"""A cell at a size the CPU holds, for driving the harness in tests."""
import copy
import json
import os

import jax

from harness import manifest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {"batch": 64, "d_in": 32, "d_hidden": 256, "d_out": 4,
        "grid": [2, 2, 2, 1]}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def config(name: str, **over) -> dict:
    with open(os.path.join(BENCH_DIR, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg.update(copy.deepcopy(TINY))
    cfg["lr"] = 16.0 / (cfg["batch"] * cfg["d_hidden"])
    # the CPU's dot takes float32 operands whole, where the chip's rounds
    # them to bfloat16
    cfg["precision"] = {**cfg["precision"], "stated": "float32"}
    cfg.update(over)
    return cfg


def traffic(name: str, **over) -> dict:
    with open(os.path.join(BENCH_DIR, "traffic", f"{name}.json")) as f:
        t = json.load(f)
    t.update(over)
    return t


def cell(config_name: str, traffic_name: str, **traffic_over):
    return manifest.Cell(f"tiny-{traffic_name}", 1, config(config_name),
                         traffic(traffic_name, **traffic_over), [], [])


def devices():
    return jax.devices()[:1]


def frozen(step):
    """``TraTrainer.step`` that returns its state unchanged."""
    def patched(self, **data):
        params, state = self.params, self.state
        loss = step(self, **data)
        self.params, self.state = params, state
        return loss
    return patched


def half_batch(step):
    """``TraTrainer.step`` that sees the first half of its batch twice:
    the sum taken over half of the batch, doubled."""
    from repro.core import TensorRelation

    def first_half(rel):
        d = rel.data
        h = d.shape[0] // 2
        return TensorRelation(d.at[h:].set(d[:h]), rel.rtype)

    def patched(self, **data):
        return step(self, **{k: first_half(v) for k, v in data.items()})
    return patched
