"""Readings from which a cell's correctness limits are set; not part of a
benchmark run.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 7,8,9 [--seconds 5] [--out readings.jsonl]

For each of ``--seeds`` it makes a whole run of the cell (``--seconds``
long, set-up and check included) and prints the numbers compared: the
largest of these over a dozen seeds or more is a number's lower reading.
For each of ``--control-seeds`` it puts the reference, computed in a
lower precision (``bfloat16``, ``int8`` and, to show the program's own
precision, ``bfloat16_operands``), in the program's place and prints the
same numbers against the float32 reference; a train cell also gets the
reference with half of each batch left out and, on a mesh, the one whose
sites leave out the exchange of their gradients.  The smallest of these is a
number's upper reading.  Everything runs in this one process, on the
chips the cell asks for.
"""
import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.join(os.path.dirname(BENCH_DIR), "src")]

from run import enable_compile_cache  # noqa: E402

CONTROLS = ("bfloat16", "int8", "bfloat16_operands")


def _emit(out, record) -> None:
    line = json.dumps(record)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def control_numbers(cell, seed: int, n_requests: int):
    """{variant: numbers} of the reference put in the program's place."""
    cfg, traffic, model = cell.config, cell.traffic, cell.model
    out = {}
    if traffic["kind"] == "train":
        def ref(**kw):
            return model.reference_train(
                cfg, seed, traffic["minibatches"], traffic["check_steps"],
                **kw)
        want = ref()
        for p in CONTROLS:
            out[p] = model.train_numbers(ref(precision=p), want)
        out["half_batch"] = model.train_numbers(ref(fault="half_batch"), want)
        if "mesh" in cfg:
            out["no_exchange"] = model.train_numbers(ref(fault="no_exchange"),
                                                     want)
    else:
        payloads = model.make_requests(cfg, seed, n_requests)
        want = model.reference_scores(cfg, seed, payloads)
        stated = model.reference_scores(cfg, seed, payloads,
                                        precision=cfg["precision"]["stated"])
        for p in CONTROLS:
            out[p] = model.score_numbers(
                model.reference_scores(cfg, seed, payloads, precision=p),
                want, stated)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from harness import device, load, manifest, runner

    cell = manifest.load_cell(args.workload)
    enable_compile_cache()
    try:
        devices = device.chips(cell.chips)
    except device.NoChip as err:
        print(f"control: {err}", file=sys.stderr)
        return 2
    peaks = device.peaks(devices[0].device_kind)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    for seed in seeds:
        t0 = time.perf_counter()
        run = runner.run_cell(cell, seed, args.seconds, False, devices,
                              peaks, t0)
        _emit(args.out, {"workload": cell.name, "seed": seed,
                         "kind": "program",
                         "numbers": {k: v for k, (v, _) in run.checks.items()},
                         "end_to_end": runner.end_to_end(run),
                         "notes": run.notes,
                         "seconds": time.perf_counter() - t0})
    n_requests = 1
    if cell.traffic["kind"] == "open_loop":
        n_requests = len(load.arrivals(cell.traffic, args.seconds))
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        t0 = time.perf_counter()
        for variant, numbers in control_numbers(cell, seed,
                                                n_requests).items():
            _emit(args.out, {"workload": cell.name, "seed": seed,
                             "kind": variant, "numbers": numbers})
        print(f"control seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
