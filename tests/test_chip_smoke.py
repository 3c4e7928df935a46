"""``chip_smoke.py``'s phases at tiny widths on the CPU.

The script proves the main path on a TPU at the paper's widths; here the
same phase functions run at a size the CPU handles, against the same
references, so a broken phase shows before any chip time is spent.
"""
import importlib.util
import os
import subprocess
import sys

import pytest

from repro.configs.ffnn_paper import FFNNConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = FFNNConfig("ffnn-tiny", d_in=32, d_hidden=256, d_out=8, batch=16)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_phase_matches_reference(smoke):
    tra = smoke.train_phase(TINY)
    assert len(tra["losses"]) == smoke.STEPS
    assert tra["losses"][-1] < tra["losses"][0]
    assert tra["fused_join_agg_nodes"] >= 2
    # the CPU compiles XLA's own matmul, not the Pallas kernel
    assert tra["tpu_custom_calls"] == 0


def test_serve_phase_covers_buckets_and_matches_oracle(smoke):
    out = smoke.serve_phase(TINY)
    assert out["answered"] == 16
    assert out["max_err"] < 1e-5


def test_serve_phase_rejects_groups_missing_a_bucket(smoke):
    with pytest.raises(ValueError, match="miss buckets"):
        smoke.serve_phase(TINY, groups=(1, 2))


def test_check_update_rejects_a_wrong_update(smoke):
    import numpy as np
    start = np.zeros((4, 4), np.float32)
    want = np.ones((4, 4), np.float32)
    smoke.check_update("ok", want * (1 + 1e-3), want, start, 1e-2, 1)
    with pytest.raises(AssertionError, match="differs"):
        smoke.check_update("bad", want * 1.1, want, start, 1e-2, 1)


def test_main_fails_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    assert '"ok"' not in out


def test_four_chip_phase_on_host_devices():
    """The --four-chips path on four virtual CPU devices: gspmd and
    shard_map TRA-DP steps against the one-device jit step."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    code = ("import chip_smoke\n"
            "from repro.configs.ffnn_paper import FFNNConfig\n"
            "cfg = FFNNConfig('ffnn-tiny', 32, 256, 8, 16)\n"
            "r = chip_smoke.four_chip_phase(cfg)\n"
            "assert sorted(r) == ['gspmd', 'shard_map']\n"
            "print('FOUR-SITE PHASE OK')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "FOUR-SITE PHASE OK" in proc.stdout
