"""Serving launcher: TraServer with continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --servable lm \
        --arch gemma2-2b --smoke --requests 40 --mode poisson --rate 50

Serves either the §5.3 FFNN scorer (``--servable scorer``) or the smoke
step-decode LM (``--servable lm``) through
:class:`~repro.serve.server.TraServer`: requests from the load generator
are continuously batched into long-lived compiled relational plans
(zero compile-cache misses after warmup), and the run prints tokens/s
with p50/p95/p99 of total / queue-wait / service latency.

``--dense-oracle`` keeps the previous launcher behaviour — the dense
transformer prefill + KV-cache decode loop over ``repro.models`` — as a
comparison path (with ``--mesh`` it shards cache and weights per the TRA
plan; decode forces KV sharding — see planner).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _dense_oracle(args) -> int:
    """Dense transformer prefill + decode loop (pre-TraServer launcher)."""
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={d * m} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import decode_step, init_params, prefill

    cfg = get_config(args.arch, smoke=args.smoke)
    cache_len = args.prompt_len + args.gen
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)

    sharder = None
    if args.mesh:
        from repro.configs.base import ShapeSpec
        from repro.launch.mesh import make_host_mesh
        from repro.sharding import make_sharder, plan_arch
        mesh = make_host_mesh(d, m)
        shape = ShapeSpec("serve", cache_len, args.batch, "decode")
        plan = plan_arch(cfg, shape, mesh)
        sharder = make_sharder(mesh, plan.act_axis_map)
    else:
        from repro.models.layers import no_shard
        sharder = no_shard

    B, S = args.batch, args.prompt_len
    if cfg.input_mode == "tokens":
        prompts = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        batch = {"tokens": prompts}
    else:
        batch = {"embeds": jax.random.normal(
            key, (B, S, cfg.d_model), jnp.bfloat16)}

    t0 = time.perf_counter()
    pf = jax.jit(lambda p, b: prefill(cfg, p, b, cache_len, sharder))
    logits, cache = pf(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    step = jax.jit(lambda p, c, b: decode_step(cfg, p, c, b, sharder),
                   donate_argnums=(1,))
    out_tokens = []
    tok = logits.argmax(-1).astype(jnp.int32)
    t1 = time.perf_counter()
    for _ in range(args.gen):
        if cfg.input_mode == "tokens":
            step_in = {"token": tok}
        else:
            emb = jax.random.normal(key, (B, 1, cfg.d_model), jnp.bfloat16)
            step_in = {"embed": emb}
        logits, cache = step(params, cache, step_in)
        tok = logits.argmax(-1).astype(jnp.int32)
        out_tokens.append(jax.device_get(tok)[:, 0])
    jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t1

    toks_s = B * args.gen / t_decode
    print(f"[serve] {args.arch}: prefill({B}x{S}) {t_prefill * 1e3:.1f} ms, "
          f"decode {args.gen} steps @ {toks_s:.1f} tok/s")
    print(f"[serve] sample continuation (seq 0): "
          f"{[int(t[0]) for t in out_tokens]}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--servable", choices=("lm", "scorer"), default="lm")
    ap.add_argument("--arch", default="gemma2-2b",
                    help="model config sizing the LM servable / dense path")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--executor", default="jit",
                    help="TRA engine executor (reference | jit | ...)")
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--capacity", type=int, default=8,
                    help="decode slots (lm servable)")
    ap.add_argument("--mode", choices=("poisson", "closed"),
                    default="poisson")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="poisson arrival rate, requests/s")
    ap.add_argument("--concurrency", type=int, default=8,
                    help="closed-loop outstanding requests")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-pending", type=int, default=None,
                    help="admission bound: shed submissions over this "
                         "many pending requests (ServerOverloaded)")
    ap.add_argument("--max-queue-wait", type=float, default=None,
                    help="shed requests queued longer than this (s)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline (s), scheduler-enforced")
    ap.add_argument("--retries", type=int, default=3,
                    help="per-request transient-fault retry budget")
    ap.add_argument("--json", action="store_true",
                    help="emit the full report as JSON")
    ap.add_argument("--dense-oracle", action="store_true",
                    help="run the dense transformer prefill/decode loop "
                         "instead of TraServer (comparison path)")
    ap.add_argument("--batch", type=int, default=4,
                    help="dense-oracle batch size")
    ap.add_argument("--mesh", default=None,
                    help="dense-oracle mesh, e.g. 2x2")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.dense_oracle:
        return _dense_oracle(args)

    import numpy as np

    from repro.core import Engine
    from repro.serve import (FFNNScorer, RecurrentLM, TraServer,
                             closed_loop, lm_mix, open_loop,
                             poisson_arrivals, scorer_mix)

    rng = np.random.default_rng(args.seed)
    engine = Engine(executor=args.executor)
    if args.servable == "scorer":
        servable = FFNNScorer(seed=args.seed)
        payloads = scorer_mix(servable, rng, args.requests)
    else:
        from repro.configs import get_config
        cfg = get_config(args.arch, smoke=args.smoke)
        servable = RecurrentLM.from_config(cfg, capacity=args.capacity,
                                           seed=args.seed)
        payloads = lm_mix(servable, rng, args.requests,
                          prompt_len=(1, max(1, args.prompt_len)),
                          new_tokens=(1, max(1, args.gen)))

    server = TraServer(engine, servable,
                       max_pending=args.max_pending,
                       max_queue_wait_s=args.max_queue_wait,
                       max_retries=args.retries)
    server.warmup()
    if args.mode == "poisson":
        arrivals = poisson_arrivals(rng, args.requests, args.rate)
        report = open_loop(server, payloads, arrivals,
                           deadline_s=args.deadline)
    else:
        report = closed_loop(server, lambda i: payloads[i],
                             n_requests=args.requests,
                             concurrency=args.concurrency)

    stats = server.stats()
    out = {**report.to_json(),
           "cache_misses_since_warmup": stats["cache_misses_since_warmup"],
           "artifacts": stats["artifacts"],
           "health": stats["health"]}
    if args.json:
        print(json.dumps(out, indent=2))
    else:
        t = out["total_ms"]
        print(f"[serve] {servable.name} on {engine.executor}: "
              f"{report.requests} requests ({report.errors} errors, "
              f"{report.shed} shed), {out['tokens_per_s']:.1f} tok/s")
        print(f"[serve] latency ms p50/p95/p99 = "
              f"{t['p50']:.1f}/{t['p95']:.1f}/{t['p99']:.1f}; "
              f"queue-wait p50 = {out['queue_wait_ms']['p50']:.1f} ms")
        print(f"[serve] artifacts: {len(out['artifacts'])} pinned, "
              f"{out['cache_misses_since_warmup']} cache misses "
              f"after warmup")
        hc = out["health"]["counters"]
        print(f"[serve] health {out['health']['status']}: "
              f"retries={hc['retries']} recovered={hc['recovered']} "
              f"shed={hc['shed']} deadline={hc['deadline_expired']}")
    return 1 if report.errors else 0


if __name__ == "__main__":
    sys.exit(main())
