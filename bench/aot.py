"""Compile every cell's programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 bench/aot.py [--workload <cell> ...]

For each cell of ``BENCHMARK.json`` (or those named) it builds the
programs the cell's window runs — the train step, or the scorer's
program for each bucket — at the configuration's own widths, compiles
them for one chip of a described ``v5e:2x2`` (or for its mesh, for a
cell on four chips), and prints ``memory_analysis()`` (per device, for a
mesh) and the number of Pallas kernels (``tpu_custom_call``) in each.
A program the chip's compiler refuses, or one that does not fit, shows
here before any chip time is spent.  Nothing runs, so nothing here is a time.
"""
import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.join(os.path.dirname(BENCH_DIR), "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _programs(cell, topo):
    """(label, compiled expression, sharding of an input by name) of
    each program the cell's window dispatches."""
    from jax.sharding import SingleDeviceSharding

    from repro.core import Engine
    cfg, model = cell.config, cell.model
    dims = model.block_dims(cfg)
    one_chip = SingleDeviceSharding(topo.devices[0])
    if "mesh" in cfg:
        engine, shardings = cell.system.mesh_engine(cfg, topo.devices)
        sharding = shardings.__getitem__
    else:
        engine, sharding = Engine(executor="jit"), lambda _: one_chip
    if cell.traffic["kind"] == "train":
        from repro.core import SGD
        from repro.core.programs import ffnn_train_step_tra
        step = ffnn_train_step_tra(*dims, optimizer=SGD(cfg["lr"]))
        yield "train step", engine.compile(step.roots), sharding
    else:
        from repro.serve import FFNNScorer
        _, db, hb, lb, _, bd, bh, bl = dims
        sv = FFNNScorer(1, 1, 1, 1, 1, 1, buckets=cell.traffic["buckets"])
        sv.db, sv.hb, sv.lb, sv.bd, sv.bh, sv.bl = db, hb, lb, bd, bh, bl
        sv.d_in, sv.d_out = db * bd, lb * bl
        for b in sv.buckets:
            yield f"scorer bucket {b}", engine.compile(sv.program(b)), sharding


def compile_cell(cell, topo) -> list:
    import jax
    import jax.numpy as jnp

    import repro.kernels.matmul.ops as ops
    ops._on_tpu = lambda: True         # the CPU answers for the chip here
    out = []
    for label, ce, sharding in _programs(cell, topo):
        args = []
        for n in ce.input_names:
            rt = ce.input_rtypes[n]
            args.append(jax.ShapeDtypeStruct(
                tuple(rt.key_shape) + tuple(rt.bound), jnp.float32,
                sharding=sharding(n)))
        compiled = ce.jitted.lower(*args).compile()
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        out.append({"cell": cell.name, "program": label,
                    "devices": cell.chips,
                    "argument_bytes": ma.argument_size_in_bytes,
                    "output_bytes": ma.output_size_in_bytes,
                    "temp_bytes": ma.temp_size_in_bytes,
                    "total_bytes": ma.argument_size_in_bytes
                    + ma.output_size_in_bytes + ma.temp_size_in_bytes,
                    "tpu_custom_calls": text.count("tpu_custom_call"),
                    "kernels": sorted({line.split("=")[0].strip()
                                       for line in text.splitlines()
                                       if "custom_call_target=\"tpu_custom_call\""
                                       in line})})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", default=[])
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies

    from harness import manifest

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = args.workload or [w["name"] for w in
                               manifest.load_manifest()["workloads"]]
    for name in names:
        cell = manifest.load_cell(name)
        for record in compile_cell(cell, topo):
            print(json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
