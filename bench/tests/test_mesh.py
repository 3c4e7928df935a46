"""The four-chip cell's pieces at a size the CPU holds.

A run on a mesh of four virtual CPU devices (in a child process, since
the device count is fixed when JAX starts) is correct without a fault and
not correct with each fault planted in the program: a step that returns
its state unchanged, half of the batch left out, and the exchange of the
gradients between the sites left out.  The blocked reference equals the
unblocked one; the bfloat16 control and the reference without the
exchange fail the limits; ``collective_ms.train`` reads a synthetic
trace."""
import contextlib
import json
import os
import subprocess
import sys
import time
import types

import pytest

import tiny
from harness import manifest, runner
from harness import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
SITES = 4
FAULTS = ("none", "state_unchanged", "half_batch", "no_exchange")


def mesh_config(**over) -> dict:
    """speech-200k's configuration at the CPU's size: one key block of
    N, D and H per site."""
    return tiny.config("speech-200k", grid=[SITES, SITES, SITES, 1],
                       reference_rows_per_block=16, **over)


@contextlib.contextmanager
def planted(fault: str):
    """``TraTrainer.step`` with ``fault`` planted, for the block."""
    import jax.numpy as jnp

    from repro.core import TensorRelation, TraTrainer
    step = TraTrainer.step

    def no_exchange(self, **data):
        # each site's stored share of a weight (key dim 0) takes the step
        # of the batch with every other site's rows of X zeroed, which
        # add nothing to any gradient of the FFNN
        params, state = self.params, self.state
        loss = step(self, **data)
        after = self.state
        shares = {n: [] for n in params}
        x = data["X"]
        for site in range(SITES):
            keep = jnp.arange(x.data.shape[0]) * SITES // x.data.shape[0]
            mine = jnp.where((keep == site)[:, None, None, None], x.data, 0)
            self.params, self.state = params, state
            step(self, **{**data, "X": TensorRelation(mine, x.rtype)})
            for n, r in self.params.items():
                shares[n].append(jnp.split(r.data, SITES)[site])
        self.params = {n: TensorRelation(jnp.concatenate(shares[n]),
                                         params[n].rtype) for n in params}
        self.state = after
        return loss

    patch = {"none": step, "state_unchanged": tiny.frozen(step),
             "half_batch": tiny.half_batch(step),
             "no_exchange": no_exchange}[fault]
    TraTrainer.step = patch
    try:
        yield
    finally:
        TraTrainer.step = step


def four_site_runs() -> None:
    """One run of the tiny mesh cell per fault, printed as one JSON line
    {fault: [correct, {number: [value, limit]}]}; run on four devices."""
    import jax
    cell = manifest.Cell("tiny-mesh", SITES, mesh_config(),
                         tiny.traffic("train"), [], [])
    devices = jax.devices()[:SITES]
    assert len(devices) == SITES, devices
    out = {}
    for fault in FAULTS:
        with planted(fault):
            run = runner.run_cell(cell, 2**33 + 7, 0.5, False, devices,
                                  tiny.PEAKS, time.perf_counter())
        out[fault] = [run.correct, run.checks]
    print(json.dumps(out))


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={SITES} "
                        + env.get("XLA_FLAGS", ""))
    bench = os.path.dirname(HERE)
    env["PYTHONPATH"] = os.pathsep.join(
        [HERE, bench, os.path.join(os.path.dirname(bench), "src")])
    proc = subprocess.run(
        [sys.executable, "-c", "import test_mesh; test_mesh.four_site_runs()"],
        cwd=HERE, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_on_four_sites_is_correct(runs):
    correct, checks = runs["none"]
    assert correct, checks


@pytest.mark.parametrize("fault", FAULTS[1:])
def test_fault_on_four_sites_is_caught(runs, fault):
    correct, checks = runs[fault]
    assert not correct, checks


def test_blocked_reference_equals_the_unblocked_one():
    from models import ffnn
    blocked = mesh_config()
    whole = mesh_config()
    del whole["reference_rows_per_block"]
    got = ffnn.reference_train(blocked, 2**34 + 3, 3, 3)
    want = ffnn.reference_train(whole, 2**34 + 3, 3, 3)
    assert ffnn.loss_gap(got["losses"], want["losses"]) < 1e-6
    for k in ("grad_norms", "change_norms"):
        assert ffnn.norm_gap(got[k], want[k]) < 1e-6, (got[k], want[k])


def test_control_and_no_exchange_fail_the_limits():
    from control import control_numbers
    cell = manifest.Cell("tiny-mesh", SITES, mesh_config(lr=1e-6),
                         tiny.traffic("train"), [], [])
    limits = cell.config["limits"]["train"]
    got = control_numbers(cell, 2**35 + 1, 0)

    def fails(numbers):
        return any(v > limits[k] for k, v in numbers.items())

    assert fails(got[cell.config["precision"]["control_precision"]["train"]])
    assert fails(got["no_exchange"]), got
    assert fails(got["half_batch"]), got


def _event(name, start_ms, end_ms):
    return tr.Event(name, start_ms * 1e-3, end_ms * 1e-3, {})


def test_collective_ms_reads_a_synthetic_trace():
    """Two devices over two steps: each holds one synchronous all-gather
    (4 ms), one asynchronous pair (start 1 ms, done 2 ms), a compute
    fusion that carries the exchange in flight and a plain product, which
    do not count; a fusion that calls the all-reduce-scatter (3 ms) on
    device 1 only."""
    ops = {}
    for d in (0, 1):
        ops[d] = [
            _event("%all-gather.24 = bf16[4,4,2500,400]{2,3,1,0:T(8,128)} "
                   "all-gather(%convert.1), channel_id=1", 0, 4),
            _event("%async-collective-start = (f32[1,10]{1,0}, "
                   "f32[4,10]{1,0}) fusion(%p), kind=kCustom, "
                   "calls=%fused_computation.34", 5, 6),
            _event("%fusion.46 = (f32[4,2500,50000]{2,1,0}, f32[4,10]{1,0}) "
                   "fusion(%a, %b), kind=kOutput, "
                   "calls=%async_collective_fusion.46", 6, 20),
            _event("%async-collective-done = f32[4,10]{1,0} fusion(%g), "
                   "kind=kCustom, calls=%fused_computation.37", 20, 22),
            _event("%dot.3 = f32[8,8]{1,0} dot(%x, %y)", 22, 30)]
    ops[1].append(_event("%fusion = f32[40,8,128]{2,1,0} fusion(%g.46), "
                         "kind=kCustom, calls=%all-reduce-scatter", 30, 33))
    window = tr.Event("bench.window", 0.0, 0.040, {})
    run = types.SimpleNamespace(
        train=types.SimpleNamespace(steps=2),
        trace=tr.Trace(ops, [window]), device_ids=[0, 1])
    read = manifest.metric_reader("collective_ms.train").read
    # (4 + 1 + 2) ms on each device, 3 ms more on one: 17 ms over two
    # devices and two steps
    assert read(run) == pytest.approx(17 / 2 / 2)
    for d in (0, 1):
        ops[d] = [e for e in ops[d] if "dot" in e.name or "%fusion.46" in
                  e.name]
    assert read(run) is None
