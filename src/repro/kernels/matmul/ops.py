"""Public matmul op: pads to block multiples, dispatches pallas vs jnp.

``impl="auto"`` uses the Pallas kernel on TPU backends and the jnp oracle
elsewhere (CPU dry-runs and tests lower through XLA's own matmul, which is
what a CPU run would use anyway; the kernel path is validated separately in
interpret mode).

An operand of the kernel that stays the same from call to call can be
padded once, by :func:`pad_operand` to :func:`tile_multiples`, and passed
in its padded form: ``matmul`` pads only what is not yet a multiple of its
blocks, and ``out_shape`` gives the product's unpadded shape.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.matmul.kernel import matmul_pallas
from repro.kernels.matmul.ref import matmul_ref

# the named scope of the copies that move operands between a relation's
# blocks and the kernel's padded 2-D layout: their device ops carry it
LAYOUT_SCOPE = "tra.layout"

BLOCK = 512                 # matmul's default block size along each dim


def pad_operand(x: jax.Array, tile: Tuple[int, int]) -> jax.Array:
    """``x`` zero-padded at the end of each dim to a multiple of ``tile``."""
    p0 = (-x.shape[0]) % tile[0]
    p1 = (-x.shape[1]) % tile[1]
    if p0 or p1:
        with jax.named_scope(LAYOUT_SCOPE):
            x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def tile_multiples(shape: Tuple[int, int], impl: str = "auto"
                   ) -> Optional[Tuple[int, int]]:
    """The multiples ``matmul`` with its default blocks pads a 2-D operand
    of ``shape`` to, for the kernel; None where XLA's dot takes the
    operand as it is.  A dim padded to its multiple keeps its multiple,
    so an operand padded to these is left as it is."""
    if _resolve(impl) != "pallas":
        return None
    return (min(BLOCK, shape[0]), min(BLOCK, shape[1]))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "jnp"
    return impl


@functools.partial(jax.jit, static_argnames=("impl", "block_m", "block_n",
                                             "block_k", "interpret",
                                             "out_shape"))
def matmul(a: jax.Array, b: jax.Array, *, impl: str = "auto",
           block_m: int = BLOCK, block_n: int = BLOCK, block_k: int = BLOCK,
           interpret: bool = False,
           out_shape: Optional[Tuple[int, int]] = None) -> jax.Array:
    impl = _resolve(impl)
    if impl == "jnp":
        return matmul_ref(a, b)
    if impl != "pallas":
        raise ValueError(impl)
    m, n = out_shape or (a.shape[0], b.shape[1])
    bm, bn, bk = (min(block_m, m), min(block_n, n), min(block_k, a.shape[1]))
    ap = pad_operand(a, (bm, bk))
    bp = pad_operand(b, (bk, bn))
    out = matmul_pallas(ap, bp, block_m=bm, block_n=bn, block_k=bk,
                        interpret=interpret)
    with jax.named_scope(LAYOUT_SCOPE):
        return out[:m, :n]
