"""Benchmark runner: one section per paper table + the roofline report.

    PYTHONPATH=src python -m benchmarks.run [--with-measured]

``--with-measured`` additionally executes the scaled-down distributed
plans on an 8-host-device mesh (slower; spawns a subprocess so the main
process keeps its single-device view).  That subprocess is a host-device
simulation: it runs with ``JAX_PLATFORMS=cpu``, so it never competes
with this process for an accelerator.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--with-measured", action="store_true")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (analysis, ffnn, fusion, matmul, nn_search,
                            oocore, resilience, robustness, roofline,
                            serve, train)

    sections = [
        ("§5.1 matmul (Tables 3–4)", matmul.run),
        ("§5.2 nn-search (Tables 5–6)", nn_search.run),
        ("§5.3 ffnn (Tables 7–9)", ffnn.run),
        ("fused Σ∘⋈ contraction (BENCH_fusion.json)", fusion.run),
        ("TRA train step (BENCH_train.json)", train.run),
        ("robustness overheads (BENCH_robust.json)", robustness.run),
        ("serving: continuous batching (BENCH_serve.json)", serve.run),
        ("serving resilience (BENCH_resilience.json)", resilience.run),
        ("out-of-core streaming (BENCH_oocore.json)", oocore.run),
        ("static verifier overhead (BENCH_analysis.json)", analysis.run),
        ("roofline (assignment g)", roofline.run),
    ]
    failures = 0
    for title, fn in sections:
        print(f"\n{'=' * 72}\n{title}\n{'=' * 72}")
        try:
            for line in fn(None):
                print(line)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"SECTION FAILED: {e!r}")

    if args.with_measured:
        print(f"\n{'=' * 72}\nmeasured 8-device runs (subprocess, "
              f"simulated on 8 CPU host devices, JAX_PLATFORMS=cpu)\n"
              f"{'=' * 72}")
        code = (
            "import os;"
            "os.environ['XLA_FLAGS']="
            "'--xla_force_host_platform_device_count=8';"
            "import jax;"
            "from benchmarks import matmul;"
            "from repro.launch.mesh import make_mesh;"
            "mesh = make_mesh((8,), ('sites',));"
            "print('\\n'.join(str(r) for r in matmul.measured(mesh)))")
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=1200,
                              env={**os.environ, "JAX_PLATFORMS": "cpu"})
        print(proc.stdout or proc.stderr)
        failures += proc.returncode != 0
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
