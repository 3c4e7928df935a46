"""GPipe-style pipeline parallelism over a mesh axis.

The paper (§6) notes pipelined model parallelism is the database
community's *inter-operation parallelism*; in TRA terms each stage is a
site-partitioned relation of layer weights (``PART_{stage}``) and the
activation handoff is a ``SHUF`` on the stage key dim.  Here the handoff
is the TPU-idiomatic ``jax.lax.ppermute`` ring step inside ``shard_map``.

Schedule: plain GPipe fill-drain over ``M`` microbatches and ``S`` stages
(M + S − 1 ticks).  Bubble fraction = (S−1)/(M+S−1); callers pick M ≫ S.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(stage_fn: Callable, mesh: Mesh, stage_axis: str):
    """Build a pipelined ``(stacked_params, microbatches) -> outputs`` fn.

    ``stage_fn(params_slice, x) -> y`` maps one stage over one microbatch
    (x and y must share shape/dtype).  ``stacked_params`` leaves have a
    leading stage dim (== mesh.shape[stage_axis]); ``microbatches`` is
    ``(M, B, ...)``.  Returns outputs ``(M, B, ...)`` after all stages.
    """
    S = mesh.shape[stage_axis]

    def local(params, xs):
        # inside shard_map: params leaves (1, ...) — this stage's slice
        params = jax.tree.map(lambda l: l[0], params)
        M = xs.shape[0]
        stage = jax.lax.axis_index(stage_axis)
        ticks = M + S - 1
        buf = jnp.zeros_like(xs[0])                  # incoming activation
        outs = jnp.zeros_like(xs)
        # carries become stage-varying after the first ppermute
        buf = jax.lax.pcast(buf, (stage_axis,), to="varying")
        outs = jax.lax.pcast(outs, (stage_axis,), to="varying")

        def tick(t, carry):
            buf, outs = carry
            mb = jnp.clip(t, 0, M - 1)
            inp = jnp.where(stage == 0,
                            xs[mb].astype(buf.dtype), buf)
            y = stage_fn(params, inp)
            # pass activations down the ring (last stage's send unused)
            nxt = jax.lax.ppermute(
                y, stage_axis, [(i, (i + 1) % S) for i in range(S)])
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            take = (stage == S - 1) & (t >= S - 1)
            upd = jnp.where(take, y, outs[out_idx])
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, upd.astype(outs.dtype), out_idx, 0)
            return nxt, outs

        buf, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # only the last stage holds real outputs: sum the one-hot stack
        outs = jnp.where(stage == S - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, stage_axis)

    def run(stacked_params, microbatches):
        pspec = jax.tree.map(lambda _: P(stage_axis), stacked_params)
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(pspec, P()), out_specs=P(),
        )(stacked_params, microbatches)

    return run


def bubble_fraction(n_microbatches: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
