"""Public matmul op: pads to block multiples, dispatches pallas vs jnp.

``impl="auto"`` uses the Pallas kernel on TPU backends and the jnp oracle
elsewhere (CPU dry-runs and tests lower through XLA's own matmul, which is
what a CPU run would use anyway; the kernel path is validated separately in
interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.matmul.kernel import matmul_pallas
from repro.kernels.matmul.ref import matmul_ref

# the named scope of the copies that move operands between a relation's
# blocks and the kernel's padded 2-D layout: their device ops carry it
LAYOUT_SCOPE = "tra.layout"


def _pad_to(x: jax.Array, mult0: int, mult1: int) -> jax.Array:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        with jax.named_scope(LAYOUT_SCOPE):
            x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("impl", "block_m", "block_n",
                                             "block_k", "interpret"))
def matmul(a: jax.Array, b: jax.Array, *, impl: str = "auto",
           block_m: int = 512, block_n: int = 512, block_k: int = 512,
           interpret: bool = False) -> jax.Array:
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "jnp"
    if impl == "jnp":
        return matmul_ref(a, b)
    if impl != "pallas":
        raise ValueError(impl)
    m, n = a.shape[0], b.shape[1]
    bm, bn, bk = (min(block_m, m), min(block_n, n), min(block_k, a.shape[1]))
    ap = _pad_to(a, bm, bk)
    bp = _pad_to(b, bk, bn)
    out = matmul_pallas(ap, bp, block_m=bm, block_n=bn, block_k=bk,
                        interpret=interpret)
    with jax.named_scope(LAYOUT_SCOPE):
        return out[:m, :n]
