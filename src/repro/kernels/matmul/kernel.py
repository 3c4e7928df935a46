"""Blocked matmul Pallas TPU kernel — the TRA local-join hot spot.

The paper's ``⋈ᴸ`` applies an opaque MKL/CUDA ``matMul`` kernel per joined
block pair.  On TPU the analogous hot spot is an MXU-tiled block matmul:

* grid ``(M/bm, N/bn, K/bk)`` with the contraction dim innermost so the
  f32 accumulator lives in VMEM scratch across the K sweep,
* 128-aligned block shapes so every ``jnp.dot`` maps onto full MXU passes,
* inputs stay in their storage dtype (bf16 on TPU) and accumulate in f32
  (``preferred_element_type``), written back in the output dtype.

VMEM budget per core: ``bm*bk + bk*bn`` input tiles + ``bm*bn`` f32
accumulator; the default 512×512×512 tiling costs ~2.6 MB of the ~16 MB
VMEM, leaving headroom for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(a: jax.Array, b: jax.Array, *, block_m: int = 512,
                  block_n: int = 512, block_k: int = 512,
                  out_dtype=None, interpret: bool = False) -> jax.Array:
    """``a @ b`` for 2-D operands with MXU-aligned tiling.

    Shapes must divide the block sizes (the ops.py wrapper pads).
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} x {b.shape}")
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    if m % block_m or n % block_n or k % block_k:
        raise ValueError("shapes must divide block sizes (pad in ops.py)")
    out_dtype = out_dtype or a.dtype
    # inside shard_map the output varies over the mesh axes its operands
    # vary over (the empty set outside one)
    vma = jax.typeof(a).vma | jax.typeof(b).vma
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, l: (i, l)),
            pl.BlockSpec((block_k, block_n), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(a, b)
