"""Run the TRA engine's main path on a TPU at the paper's own widths.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --four-chips   # TRA-DP step on four chips vs one

The model is the paper's §5.3 two-layer FFNN on the Google-speech data
(``configs/ffnn_paper.speech(100_000)``: D=1600 features, H=100,000
hidden units, L=10 labels, minibatch N=10^4), at full width and with
random weights and data made from ``--seed``.

* **Phase A (train).**  ``ffnn_train_step_tra`` through
  ``Engine(executor="jit")`` and ``TraTrainer`` for a few SGD steps.  It
  prints how many Pallas ``tpu_custom_call`` s the compiled step holds
  (zero fails), the compile and per-step seconds and the device's peak
  bytes, then checks every step's loss and the weight updates against a
  plain ``jax.numpy`` float32 SGD step.
* **Phase B (serve).**  ``FFNNScorer`` at the same widths behind
  ``TraServer``; every bucket of ``DEFAULT_BUCKETS`` serves, every answer
  must match the scorer's dense oracle, and no request may be shed,
  failed or retried.
* **--four-chips.**  Only the paper's distributed claim: the same step
  under TRA-DP placements on a ``("sites",)`` mesh of four chips, through
  ``executor="gspmd"`` and ``executor="shard_map"``, each compared with
  the one-chip ``jit`` step on the same inputs, with inputs and outputs
  shown spread over the four devices.

Everything runs in this one process.  Without a TPU the script exits 1
before any phase.  The last line of standard output is a JSON object
naming the device; a failed phase raises, exits non-zero and prints no
such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.configs.ffnn_paper import FFNNConfig, speech  # noqa: E402

# Key grids (nb, db, hb, lb) over N, D, H, L.  nb, db and hb divide by the
# four sites of --four-chips: TRA-DP partitions X, Y, W1 and W2 on dim 0.
GRID = (4, 4, 4, 1)
STEPS = 3
W1_SAMPLE_ROWS = 16
X_SCALE = 8.0

# Tolerances against the float32 references, which run at "highest"
# matmul precision.  On TPU an f32 dot at XLA's default precision runs
# as bf16 passes (unit roundoff 2^-8), the Pallas kernel's f32 jnp.dot
# need not round the same way, and the TRA step mixes both kinds of
# contraction.  Rounding every contraction operand to bf16 on the CPU,
# at N=10^4, D=1600, H=25,000, moved the loss by at most 2e-5 relative,
# the W2 update by 0.2% and the W1 update by 2.2% (relu masks flip where
# X·W1 rounds across zero), all in Frobenius norm.
LOSS_RTOL = 1e-3
W2_RTOL = 1e-2
W1_RTOL = 5e-2
# Scores are sigmoids: bf16 operand rounding moves a logit z by about
# 2^-8·|z| and a score by at most a quarter of that.
SCORE_ATOL = 1e-2


def block_dims(cfg: FFNNConfig, grid=GRID):
    """``(nb, db, hb, lb, bn, bd, bh, bl)`` of ``cfg`` cut by ``grid``."""
    sizes = (cfg.batch, cfg.d_in, cfg.d_hidden, cfg.d_out)
    for s, g in zip(sizes, grid):
        if s % g:
            raise ValueError(f"grid {grid} does not divide {sizes}")
    return tuple(grid) + tuple(s // g for s, g in zip(sizes, grid))


def sgd_lr(cfg: FFNNConfig) -> float:
    """SGD step size of the comparison run.

    The paper's η = 0.01 multiplies a loss summed over the N rows.  With
    relu activations of mean ≈ 0.4 the loss's curvature along W2 is about
    N·H·0.4²/4, so at speech-100k η = 0.01 overshoots by five orders of
    magnitude: the weights blow up in one step, the sigmoids saturate and
    a diverging trajectory amplifies rounding until nothing compares.
    16/(N·H) keeps W2's step inside the stable range at every width.
    W1's curvature grows with ``X_SCALE²`` and falls with H: its step is
    stable from H = 256 up (H = 64 diverges).  The loss falls each step.
    """
    return 16.0 / (cfg.batch * cfg.d_hidden)


def make_data(cfg: FFNNConfig, seed: int):
    """Seeded X, Y (a random teacher's sigmoid outputs), W1 and W2 made on
    the default device.

    X has standard deviation ``X_SCALE`` and W1 and the teacher are
    scaled down by as much, so X·W1 and the teacher's logits have unit
    scale.  This makes the W1 update ``X_SCALE²`` times larger against
    W1's float32 spacing: with unit X, at H = 10^5, W1 moved only about 7
    spacings in 3 steps, too little to compare.  The teacher's product
    is pinned at "highest" so the data do not depend on the ambient
    matmul precision."""
    import jax
    import jax.numpy as jnp
    N, D, H, L = cfg.batch, cfg.d_in, cfg.d_hidden, cfg.d_out
    kx, kt, k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 4)
    X = jax.random.normal(kx, (N, D), jnp.float32) * X_SCALE
    teacher = jax.random.normal(kt, (D, L), jnp.float32) \
        * (D ** -0.5 / X_SCALE)
    Y = jax.nn.sigmoid(jnp.dot(X, teacher,
                               precision=jax.lax.Precision.HIGHEST))
    W1 = jax.random.normal(k1, (D, H), jnp.float32) * (D ** -0.5 / X_SCALE)
    W2 = jax.random.normal(k2, (H, L), jnp.float32) * H ** -0.5
    return X, Y, W1, W2


def sample_rows(cfg: FFNNConfig, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    k = min(W1_SAMPLE_ROWS, cfg.d_in)
    return np.sort(rng.choice(cfg.d_in, size=k, replace=False))


def start_weights(cfg: FFNNConfig, seed: int, rows):
    """Initial W2 and sampled W1 rows on the host (updates start here)."""
    import numpy as np
    _, _, W1, W2 = make_data(cfg, seed)
    return {"W2": np.asarray(W2), "W1_rows": np.asarray(W1[rows])}


def check_update(name: str, got, want, start, rtol: float, steps: int):
    """Frobenius error of ``got`` against ``want``, relative to how far
    ``want`` moved from ``start``.  One float32 spacing per element per
    step is allowed on top: the two runs may round each updated weight
    to neighbouring floats, and at H = 10^5 the W1 update is only tens of
    spacings."""
    import numpy as np
    want64 = np.asarray(want, np.float64)
    err = float(np.linalg.norm(np.asarray(got, np.float64) - want64))
    moved = float(np.linalg.norm(want64 - np.asarray(start, np.float64)))
    spacing = steps * float(np.linalg.norm(
        np.spacing(np.abs(np.asarray(want, np.float32))).astype(np.float64)))
    bound = rtol * moved + spacing
    print(f"  {name}: |err| {err:.6g}, |update| {moved:.6g}, "
          f"rel {err / moved if moved else float('inf'):.6g}, "
          f"bound {bound:.6g} (rtol {rtol}, {steps} spacings)", flush=True)
    if not err <= bound:
        raise AssertionError(f"{name} differs from its reference: "
                             f"|err| {err} > bound {bound}")


def check_losses(name: str, got, want) -> None:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    rel = np.abs(got - want) / np.abs(want)
    print(f"  {name}: {got.tolist()} vs {want.tolist()}, "
          f"max rel {rel.max():.6g} (rtol {LOSS_RTOL})", flush=True)
    if not (got.shape == want.shape and np.all(rel <= LOSS_RTOL)):
        raise AssertionError(f"{name} differ from the reference")


def _host_weights(params, rows):
    """W2 and the sampled W1 rows of a TRA parameter dict, on the host."""
    import numpy as np

    from repro.core import to_tensor
    return {"W2": np.asarray(to_tensor(params["W2"])),
            "W1_rows": np.asarray(to_tensor(params["W1"])[rows])}


def _relations(cfg: FFNNConfig, dims, seed: int):
    from repro.core import from_tensor
    _, _, _, _, bn, bd, bh, bl = dims
    X, Y, W1, W2 = make_data(cfg, seed)
    data = {"X": from_tensor(X, (bn, bd)), "Y": from_tensor(Y, (bn, bl))}
    params = {"W1": from_tensor(W1, (bd, bh)),
              "W2": from_tensor(W2, (bh, bl))}
    return data, params


def _tra_train(cfg: FFNNConfig, dims, *, steps: int, seed: int, rows,
               engine, place=None):
    """Run ``steps`` TRA SGD steps on ``engine``; returns host results.

    ``place`` (optional) maps each input relation to its device layout
    before the first step."""
    import jax

    from repro.core import SGD, TraTrainer
    from repro.core.programs import ffnn_train_step_tra

    step = ffnn_train_step_tra(*dims, optimizer=SGD(sgd_lr(cfg)))
    data, params = _relations(cfg, dims, seed)
    if place is not None:
        data = {n: place(r) for n, r in data.items()}
        params = {n: place(r) for n, r in params.items()}
    trainer = TraTrainer(engine, step, params=params)
    del params

    t0 = time.perf_counter()
    compiled = engine.compile(step.roots)
    info = {"fused_join_agg_nodes": compiled.describe().count("FusedJoinAgg")}
    if compiled.jitted is not None:
        env = {**trainer.params, **trainer.state, **data}
        xla = compiled.jitted.lower(
            *(env[n].data for n in compiled.input_names)).compile()
        info["tpu_custom_calls"] = xla.as_text().count("tpu_custom_call")
        ma = xla.memory_analysis()
        info["step_bytes"] = (ma.argument_size_in_bytes
                              + ma.output_size_in_bytes
                              + ma.temp_size_in_bytes)
        del env, xla
    info["compile_s"] = time.perf_counter() - t0

    step_s = []
    for _ in range(steps):
        t0 = time.perf_counter()
        trainer.step(**data)
        jax.block_until_ready(trainer.params["W1"].data)
        step_s.append(time.perf_counter() - t0)
    info["step_s"] = step_s
    info["losses"] = list(trainer.history)
    info["inputs"] = {n: r.data for n, r in {**data, **trainer.params}.items()}
    info.update(_host_weights(trainer.params, rows))
    info["memory_stats"] = jax.devices()[0].memory_stats()  # None on CPU
    return info


def reference_sgd(cfg: FFNNConfig, *, steps: int, seed: int, rows):
    """The same SGD steps in plain ``jax.numpy`` float32 at "highest"
    precision: losses, final W2 and the sampled W1 rows, on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    X, Y, W1, W2 = make_data(cfg, seed)
    lr = sgd_lr(cfg)

    def step(X, Y, W1, W2):
        a1 = jax.nn.relu(X @ W1)
        a2 = jax.nn.sigmoid(a1 @ W2)
        p = jnp.clip(a2, 1e-7, 1.0 - 1e-7)
        loss = jnp.sum(-(Y * jnp.log(p) + (1.0 - Y) * jnp.log1p(-p)))
        d2 = a2 - Y
        g2 = a1.T @ d2
        g1 = X.T @ ((d2 @ W2.T) * (a1 > 0))
        return loss, W1 - lr * g1, W2 - lr * g2

    losses = []
    with jax.default_matmul_precision("highest"):
        jstep = jax.jit(step)
        for _ in range(steps):
            loss, W1, W2 = jstep(X, Y, W1, W2)
            losses.append(float(loss))
    return {"losses": losses, "W2": np.asarray(W2),
            "W1_rows": np.asarray(W1[rows])}


def train_phase(cfg: FFNNConfig, grid=GRID, *, steps: int = STEPS,
                seed: int = 0):
    """Phase A: ``steps`` TRA SGD steps on one device vs the reference.

    The TRA run and the reference do not fit the chip together, so the
    TRA side keeps only the compared values, is freed, and then the
    reference runs.  Returns the TRA run's record."""
    from repro.core import Engine

    dims = block_dims(cfg, grid)
    rows = sample_rows(cfg, seed)
    print(f"[A] train {cfg.name}: N={cfg.batch} D={cfg.d_in} "
          f"H={cfg.d_hidden} L={cfg.d_out}, blocks {dims}, "
          f"SGD lr {sgd_lr(cfg):.6g}, {steps} steps", flush=True)
    tra = _tra_train(cfg, dims, steps=steps, seed=seed, rows=rows,
                     engine=Engine(executor="jit"))
    del tra["inputs"]
    gc.collect()
    print(f"  compiled step: {tra['fused_join_agg_nodes']} FusedJoinAgg "
          f"nodes, {tra['tpu_custom_calls']} tpu_custom_call, compile "
          f"{tra['compile_s']:.3f} s", flush=True)
    print(f"  step wall s: {tra['step_s']}", flush=True)
    print(f"  compiled step arguments+outputs+temporaries "
          f"{tra['step_bytes']} bytes", flush=True)
    if tra["memory_stats"]:
        print(f"  peak_bytes_in_use {tra['memory_stats']['peak_bytes_in_use']}"
              f"; memory_stats {tra['memory_stats']}", flush=True)
    ref = reference_sgd(cfg, steps=steps, seed=seed, rows=rows)
    start = start_weights(cfg, seed, rows)
    check_losses("losses", tra["losses"], ref["losses"])
    check_update("W2", tra["W2"], ref["W2"], start["W2"], W2_RTOL, steps)
    check_update("W1 sampled rows", tra["W1_rows"], ref["W1_rows"],
                 start["W1_rows"], W1_RTOL, steps)
    if not ref["losses"][-1] < ref["losses"][0]:
        raise AssertionError(f"reference loss did not fall: {ref['losses']}")
    return tra


def serve_phase(cfg: FFNNConfig, grid=GRID, *, seed: int = 0,
                groups=(1, 2, 3, 8, 2)):
    """Phase B: ``FFNNScorer`` at ``cfg``'s widths behind ``TraServer``.

    Each group of requests is served as one batch, so the groups cover
    every bucket; each answer must match the dense oracle and no counter
    of the server may move (no shed, failed or retried request)."""
    import jax
    import numpy as np

    from repro.core import Engine
    from repro.serve import FFNNScorer, TraServer, pick_bucket, scorer_mix

    _, db, hb, lb, _, bd, bh, bl = block_dims(cfg, grid)
    sv = FFNNScorer(db, hb, lb, bd, bh, bl, seed=seed)
    used = {pick_bucket(g, sv.buckets) for g in groups}
    if used != set(sv.buckets):
        raise ValueError(f"groups {groups} miss buckets "
                         f"{sorted(set(sv.buckets) - used)}")
    print(f"[B] serve {sv.name}: D={sv.d_in} H={hb * bh} L={sv.d_out}, "
          f"buckets {sv.buckets}, {sum(groups)} requests in batches "
          f"{groups}", flush=True)
    engine = Engine(executor="jit")
    server = TraServer(engine, sv)
    t0 = time.perf_counter()
    server.warmup()
    print(f"  warmup (plans of {len(sv.buckets)} buckets) "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    payloads = scorer_mix(sv, np.random.default_rng(seed), sum(groups))
    answers, i = [], 0
    for g in groups:
        answers += server.serve(payloads[i:i + g])
        i += g
    stats = server.stats()
    counters = stats["health"]["counters"]
    print(f"  answered {len(answers)}; counters {counters}; cache misses "
          f"since warmup {stats['cache_misses_since_warmup']}", flush=True)
    if any(counters.values()) or stats["cache_misses_since_warmup"]:
        raise AssertionError(f"server was not clean: {stats['health']}")
    for b in sv.buckets:
        aid = engine.compile(sv.program(b)).artifact_id
        if not server.dispatches.get(aid):
            raise AssertionError(f"bucket {b} never dispatched")
    with jax.default_matmul_precision("highest"):
        err = max(float(np.max(np.abs(a - sv.oracle(p))))
                  for a, p in zip(answers, payloads))
    print(f"  max |score - oracle| {err:.6g} (atol {SCORE_ATOL})",
          flush=True)
    if not err <= SCORE_ATOL:
        raise AssertionError(f"scores differ from the oracle by {err}")
    return {"answered": len(answers), "max_err": err}


def four_chip_phase(cfg: FFNNConfig, grid=GRID, *, steps: int = STEPS,
                    seed: int = 0, n_sites: int = 4):
    """The §5.3 step under TRA-DP on a ``("sites",)`` mesh of
    ``n_sites`` devices, through gspmd and shard_map, each against the
    one-device ``jit`` step on the same inputs."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import Engine, TensorRelation
    from repro.core.programs import ffnn_dp_placements
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    if len(devices) < n_sites:
        raise RuntimeError(f"--four-chips needs {n_sites} devices, "
                           f"found {len(devices)}")
    dims = block_dims(cfg, grid)
    nb, db, hb, lb = dims[:4]
    rows = sample_rows(cfg, seed)
    mesh = make_mesh((n_sites,), ("sites",))
    placements = ffnn_dp_placements(nb, db, hb, lb)
    # every DP placement is "partitioned on key dim 0 over sites"
    sharded = NamedSharding(mesh, P("sites"))
    print(f"[4] TRA-DP {cfg.name} on {n_sites} devices, blocks {dims}, "
          f"{steps} steps", flush=True)

    one = _tra_train(cfg, dims, steps=steps, seed=seed, rows=rows,
                     engine=Engine(executor="jit"))
    del one["inputs"]
    gc.collect()
    print(f"  jit (1 device): losses {one['losses']}, step wall s "
          f"{one['step_s']}", flush=True)

    def place(rel):
        return TensorRelation(jax.device_put(rel.data, sharded), rel.rtype)

    start = start_weights(cfg, seed, rows)
    results = {}
    for executor in ("gspmd", "shard_map"):
        engine = Engine(mesh, executor=executor,
                        input_placements=placements)
        run = _tra_train(cfg, dims, steps=steps, seed=seed, rows=rows,
                         engine=engine, place=place)
        arrays = run.pop("inputs")     # X, Y in; W1, W2 out of the last step
        spread = {n: len(a.sharding.device_set) for n, a in arrays.items()}
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices[:n_sites]]
        del arrays, engine
        gc.collect()
        print(f"  {executor}: losses {run['losses']}, step wall s "
              f"{run['step_s']}, compile {run['compile_s']:.3f} s",
              flush=True)
        print(f"  {executor}: devices per array {spread}; bytes_in_use "
              f"per device {in_use}", flush=True)
        if any(n != n_sites for n in spread.values()):
            raise AssertionError(f"{executor}: arrays not spread over "
                                 f"{n_sites} devices: {spread}")
        # N·D bytes is one site's quarter of the float32 X
        if in_use[0] is not None and min(in_use) < cfg.batch * cfg.d_in:
            raise AssertionError(f"{executor}: a device holds less than "
                                 f"its share of X: {in_use}")
        check_losses(f"{executor} losses vs jit", run["losses"],
                     one["losses"])
        check_update(f"{executor} W2 vs jit", run["W2"], one["W2"],
                     start["W2"], W2_RTOL, steps)
        check_update(f"{executor} W1 sampled rows vs jit", run["W1_rows"],
                     one["W1_rows"], start["W1_rows"], W1_RTOL, steps)
        results[executor] = run
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the TRA-DP step on four chips against "
                         "the one-chip step")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every weight, input and request")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; jax {jax.__version__}; compile cache "
          f"{cache_dir}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this check runs only on the chip",
              file=sys.stderr)
        return 1

    cfg = speech(100_000)
    if args.four_chips:
        four_chip_phase(cfg, seed=args.seed)
    else:
        tra = train_phase(cfg, seed=args.seed)
        if tra["tpu_custom_calls"] < 1:
            raise AssertionError("the compiled train step holds no Pallas "
                                 "kernel (tpu_custom_call)")
        serve_phase(cfg, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
