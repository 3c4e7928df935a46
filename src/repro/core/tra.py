"""Tensor relations and eager TRA operations (paper §2).

Representation
--------------
The paper's integrity constraints (key *uniqueness* + *continuity*) make a
tensor relation of type ``R^(k, r, b)`` with frontier ``f`` isomorphic to a
dense array of shape ``f ++ b`` — keys become the leading ``k`` axes.  That
is exactly the representation used here, so the whole algebra stays inside
jnp and can be jit/pjit-ed.

Relations that pass through ``σ`` (filter) or a non-bijective ``ReKey`` can
violate continuity ("holes").  Keys are *static* metadata (frontiers are
known at trace time), so holes are represented by a static numpy boolean
``mask`` over the key grid — no dynamic shapes are ever needed, matching the
paper's observation that cardinalities are exact, never estimated.

Two executors share this module:
  * the dense jnp ops below (production path, jit-able),
  * :mod:`repro.core.reference` — a dict-of-numpy tuple-at-a-time oracle used
    by the hypothesis property tests.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernels_registry import Kernel

KeyFunc = Callable[[Tuple[int, ...]], Tuple[int, ...]]
BoolFunc = Callable[[Tuple[int, ...]], bool]


@dataclasses.dataclass(frozen=True)
class RelType:
    """Static type of a tensor relation: key frontier + array bound."""

    key_shape: Tuple[int, ...]   # frontier f  (exact, by continuity)
    bound: Tuple[int, ...]       # array bound b
    dtype: object = jnp.float32

    @property
    def key_arity(self) -> int:
        return len(self.key_shape)

    @property
    def rank(self) -> int:
        return len(self.bound)

    @property
    def ntuples(self) -> int:
        return math.prod(self.key_shape) if self.key_shape else 1

    @property
    def nfloats(self) -> int:
        """Total scalar payload — the paper's exact ``n × ∏ b_i``."""
        return self.ntuples * (math.prod(self.bound) if self.bound else 1)

    def with_key_shape(self, ks: Sequence[int]) -> "RelType":
        return dataclasses.replace(self, key_shape=tuple(ks))

    def with_bound(self, b: Sequence[int]) -> "RelType":
        return dataclasses.replace(self, bound=tuple(b))


@dataclasses.dataclass
class TensorRelation:
    """A dense-backed tensor relation value."""

    data: jax.Array              # shape = key_shape + bound
    rtype: RelType
    mask: Optional[np.ndarray] = None   # static validity grid or None (=all)

    def __post_init__(self) -> None:
        expect = tuple(self.rtype.key_shape) + tuple(self.rtype.bound)
        if tuple(self.data.shape) != expect:
            raise ValueError(
                f"data shape {self.data.shape} != type shape {expect}")
        if self.mask is not None and self.mask.shape != self.rtype.key_shape:
            raise ValueError("mask shape mismatch")

    # -- conveniences -----------------------------------------------------
    @property
    def key_shape(self) -> Tuple[int, ...]:
        return self.rtype.key_shape

    @property
    def bound(self) -> Tuple[int, ...]:
        return self.rtype.bound

    def is_continuous(self) -> bool:
        return self.mask is None or bool(np.all(self.mask))

    def valid_keys(self) -> np.ndarray:
        """(n, k) int array of valid keys, row-major order."""
        if not self.key_shape:
            return np.zeros((1, 0), np.int64)
        grid = np.indices(self.key_shape).reshape(len(self.key_shape), -1).T
        if self.mask is None:
            return grid
        return grid[self.mask.reshape(-1)]

    def to_dict(self) -> dict:
        """Materialize as {key tuple: np.ndarray} (reference format)."""
        out = {}
        data = np.asarray(self.data)
        for key in self.valid_keys():
            out[tuple(int(x) for x in key)] = data[tuple(key)]
        return out


def _full_mask_and(a: Optional[np.ndarray], b: Optional[np.ndarray],
                   shape: Tuple[int, ...]) -> Optional[np.ndarray]:
    if a is None and b is None:
        return None
    aa = np.broadcast_to(a if a is not None else True, shape)
    bb = np.broadcast_to(b if b is not None else True, shape)
    return np.logical_and(aa, bb)


# ==========================================================================
# Constructors
# ==========================================================================

def from_tensor(tensor: jax.Array, tile: Sequence[int]) -> TensorRelation:
    """Chunk a dense tensor into a tensor relation with block-index keys.

    ``tile[d]`` is the block size along tensor dim ``d`` (must divide the
    dim).  Keys are block coordinates; arrays are the blocks.
    """
    tile = tuple(tile)
    if len(tile) != tensor.ndim:
        raise ValueError("tile rank mismatch")
    key_shape = []
    for d, t in enumerate(tile):
        if tensor.shape[d] % t:
            raise ValueError(f"dim {d} ({tensor.shape[d]}) not divisible by {t}")
        key_shape.append(tensor.shape[d] // t)
    # reshape (k0, t0, k1, t1, ...) then move key axes to the front
    interleaved = []
    for k, t in zip(key_shape, tile):
        interleaved += [k, t]
    x = tensor.reshape(interleaved)
    perm = list(range(0, 2 * len(tile), 2)) + list(range(1, 2 * len(tile), 2))
    x = jnp.transpose(x, perm)
    rt = RelType(tuple(key_shape), tile, tensor.dtype)
    return TensorRelation(x, rt)


def to_tensor(rel: TensorRelation,
              key_dims: Optional[Sequence[int]] = None) -> jax.Array:
    """Reassemble a continuous relation into a dense tensor.

    ``key_dims[i]`` names the array dim that key dim ``i`` blocks along
    (default: the identity, requiring key arity == rank).
    """
    if not rel.is_continuous():
        raise ValueError("cannot reassemble a relation with holes")
    k, r = rel.rtype.key_arity, rel.rtype.rank
    if key_dims is None:
        if k != r:
            raise ValueError(f"key arity {k} != rank {r}; pass key_dims")
        key_dims = tuple(range(k))
    key_dims = tuple(key_dims)
    if len(key_dims) != k or len(set(key_dims)) != k:
        raise ValueError("key_dims must name each key dim once")
    # interleave: for each array dim, optionally prefix its key dim
    perm = []
    shape = []
    for d in range(r):
        if d in key_dims:
            perm.append(key_dims.index(d))
            shape.append(rel.key_shape[key_dims.index(d)] * rel.bound[d])
        else:
            shape.append(rel.bound[d])
        perm.append(k + d)
    x = jnp.transpose(rel.data, perm)
    return x.reshape(shape)


# ==========================================================================
# TRA operations (eager, dense)
# ==========================================================================

@dataclasses.dataclass
class _JoinGeometry:
    """Key alignment shared by ``join`` and ``fused_join_agg``.

    ``ldata`` is the frontier-sliced left payload (shape ``f_out_l ++
    left.bound``); ``rdata_t`` is the right payload with its key axes moved
    into output-axis order (shape = covered-axis sizes ++ right.bound).
    ``r_shape`` is the singleton-expanded right key shape over the full
    output key grid.  Nothing here is broadcast yet — the grid is only
    materialized by ``join``, never by the fused path.
    """

    kl: int
    kr: int
    k_out: int
    f_out_l: Tuple[int, ...]
    out_key_shape: Tuple[int, ...]
    covered: Tuple[int, ...]          # output key axes the right side covers
    r_shape: Tuple[int, ...]
    ldata: jax.Array
    rdata_t: jax.Array
    lmask: Optional[np.ndarray]
    rmask_t: Optional[np.ndarray]
    r_keep: Tuple[int, ...]           # right's frontier, in its own axes
    r_order: Tuple[int, ...]          # right's key axis of each of rdata_t's


def _join_align(left: TensorRelation, right: TensorRelation,
                jkl: Tuple[int, ...], jkr: Tuple[int, ...]) -> _JoinGeometry:
    if len(jkl) != len(jkr):
        raise ValueError("join key lists must have equal length")
    kl = left.rtype.key_arity
    kr = right.rtype.key_arity
    r_nonjoin = [d for d in range(kr) if d not in jkr]

    # equi-join on a dense grid: valid range of a joined dim is the min of
    # the two frontiers (paper §4.3 rule 1)
    f_out_l = list(left.key_shape)
    for i, dl in enumerate(jkl):
        f_out_l[dl] = min(left.key_shape[dl], right.key_shape[jkr[i]])
    ldata = left.data[tuple(slice(0, f) for f in f_out_l)]
    lmask = None if left.mask is None else \
        left.mask[tuple(slice(0, f) for f in f_out_l)]

    r_slices = [slice(None)] * kr
    for i, dr in enumerate(jkr):
        r_slices[dr] = slice(0, f_out_l[jkl[i]])
    rdata = right.data[tuple(r_slices)]
    rmask = None if right.mask is None else right.mask[tuple(r_slices)]

    out_key_shape = tuple(f_out_l) + tuple(rdata.shape[d] for d in r_nonjoin)
    k_out = len(out_key_shape)

    # Align RIGHT onto the output key axes:
    #   joined right dim jkr[i]   -> output axis jkl[i]
    #   non-joined right dim d    -> output axis kl + (index in r_nonjoin)
    out_axis_of_rdim = {}
    for i, dr in enumerate(jkr):
        out_axis_of_rdim[dr] = jkl[i]
    for i, dr in enumerate(r_nonjoin):
        out_axis_of_rdim[dr] = kl + i
    order = sorted(range(kr), key=lambda d: out_axis_of_rdim[d])
    rdata_t = jnp.moveaxis(rdata, list(range(kr)),
                           [order.index(d) for d in range(kr)])
    rmask_t = None if rmask is None else np.moveaxis(
        rmask, list(range(kr)), [order.index(d) for d in range(kr)])
    # singleton axes for output key axes not covered by the right
    covered = tuple(sorted(out_axis_of_rdim.values()))
    r_shape = []
    ci = 0
    for ax in range(k_out):
        if ci < len(covered) and covered[ci] == ax:
            r_shape.append(rdata_t.shape[ci])
            ci += 1
        else:
            r_shape.append(1)
    return _JoinGeometry(kl, kr, k_out, tuple(f_out_l), out_key_shape,
                         covered, tuple(r_shape), ldata, rdata_t,
                         lmask, rmask_t, tuple(rdata.shape[:kr]),
                         tuple(order))


def join(left: TensorRelation, right: TensorRelation,
         join_keys_l: Sequence[int], join_keys_r: Sequence[int],
         kernel: Kernel) -> TensorRelation:
    """⋈_(joinKeysL, joinKeysR, projOp)(L, R).

    Output keys: all left keys (original order) then right keys with the
    joined dims dropped — the paper's natural-join convention.
    """
    jkl, jkr = tuple(join_keys_l), tuple(join_keys_r)
    g = _join_align(left, right, jkl, jkr)
    rdata_b = g.rdata_t.reshape(g.r_shape + tuple(right.bound))
    rmask_b = None if g.rmask_t is None else g.rmask_t.reshape(g.r_shape)

    # left occupies the first kl output axes
    ldata_b = g.ldata.reshape(g.f_out_l + (1,) * (g.k_out - g.kl)
                              + tuple(left.bound))

    lb = jnp.broadcast_to(ldata_b, g.out_key_shape + tuple(left.bound))
    rb = jnp.broadcast_to(rdata_b, g.out_key_shape + tuple(right.bound))
    out = kernel.apply(lb, rb)

    out_bound = kernel.out_bound(left.bound, right.bound)
    rt = RelType(g.out_key_shape, tuple(out_bound), out.dtype)
    lmask_b = None if g.lmask is None else g.lmask.reshape(
        g.f_out_l + (1,) * (g.k_out - g.kl))
    mask = _full_mask_and(lmask_b, rmask_b, g.out_key_shape)
    return TensorRelation(out, rt, mask)


def _tree_fold(blocks: jax.Array, kernel: Kernel) -> jax.Array:
    """Fold axis 0 of ``blocks`` with an associative binary kernel."""
    n = blocks.shape[0]
    while n > 1:
        half = n // 2
        a = blocks[:half]
        b = blocks[half:2 * half]
        merged = kernel.apply(a, b)
        if n % 2:
            merged = jnp.concatenate([merged, blocks[2 * half:n]], axis=0)
        blocks = merged
        n = blocks.shape[0]
    return blocks[0]


def agg(rel: TensorRelation, group_by: Sequence[int],
        kernel: Kernel) -> TensorRelation:
    """Σ_(groupByKeys, aggOp)(R)."""
    if not kernel.is_associative:
        raise ValueError(f"agg kernel {kernel.name} must be associative")
    gb = tuple(group_by)
    k = rel.rtype.key_arity
    reduce_dims = tuple(d for d in range(k) if d not in gb)
    # reorder keys: group-by dims (in requested order) first
    perm = list(gb) + list(reduce_dims)
    data = jnp.moveaxis(rel.data, perm, list(range(k)))
    out_key_shape = tuple(rel.key_shape[d] for d in gb)

    mask = rel.mask
    if mask is not None:
        mask_t = np.moveaxis(mask, perm, list(range(k)))
        if kernel.identity is None:
            raise ValueError(
                f"agg over holes needs identity for {kernel.name}")
        fill = jnp.asarray(kernel.identity, dtype=data.dtype)
        mb = mask_t.reshape(mask_t.shape + (1,) * rel.rtype.rank)
        data = jnp.where(jnp.asarray(mb), data, fill)
        out_mask = np.any(mask_t, axis=tuple(range(len(gb), k))) \
            if reduce_dims else mask_t
        if np.all(out_mask):
            out_mask = None
    else:
        out_mask = None

    axes = tuple(range(len(gb), k))
    if not axes:
        out = data
    elif kernel.reduce is not None:
        out = kernel.reduce(data, axes)
    else:
        flat = data.reshape(out_key_shape + (-1,) + tuple(rel.bound))
        flat = jnp.moveaxis(flat, len(gb), 0)
        out = _tree_fold(flat, kernel)
    rt = RelType(out_key_shape, rel.bound, out.dtype)
    return TensorRelation(out, rt, out_mask)


# ==========================================================================
# Fused join→agg (Σ∘⋈ as a blocked contraction — never materializes the
# broadcasted cross-product grid the unfused pair would build)
# ==========================================================================

# Join kernels whose Σ∘⋈ with matAdd is a pure tensor contraction.  The
# value maps (left-bound, right-bound, out-bound) dims to contraction
# letters; ``None`` marks an elementwise kernel (all bound dims shared).
_CONTRACTION_JOINS = {
    "matMul": ("mk", "kn", "mn"),
    "matTranMulL": ("km", "kn", "mn"),
    "matTranMulR": ("mk", "nk", "mn"),
    "elemMul": None,
}


def can_fuse(join_kernel: Kernel, agg_kernel: Kernel) -> bool:
    """True when ``agg(join(·, join_kernel), agg_kernel)`` has a fused
    lowering (a contraction or a streamed associative reduction)."""
    return (join_kernel.arity == 2 and agg_kernel.arity == 2
            and agg_kernel.is_associative)


def _joint_mask_grid(g: _JoinGeometry) -> Optional[np.ndarray]:
    """Joined validity grid over the full output key space (bools only —
    key-grid sized, so cheap even when the payload grid is not)."""
    if g.lmask is None and g.rmask_t is None:
        return None
    lm = (g.lmask if g.lmask is not None
          else np.ones(g.f_out_l, bool)).reshape(
        g.f_out_l + (1,) * (g.k_out - g.kl))
    rm = (g.rmask_t.reshape(g.r_shape) if g.rmask_t is not None
          else np.ones((1,) * g.k_out, bool))
    return np.broadcast_to(lm, g.out_key_shape) \
        & np.broadcast_to(rm, g.out_key_shape)


def _fused_out_mask(g: _JoinGeometry, gb: Tuple[int, ...],
                    reduce_dims: Tuple[int, ...]) -> Optional[np.ndarray]:
    """Static output mask of agg∘join."""
    jm = _joint_mask_grid(g)
    if jm is None:
        return None
    om = np.any(jm, axis=reduce_dims) if reduce_dims else jm
    remaining = [d for d in range(g.k_out) if d not in reduce_dims]
    om = om.transpose([remaining.index(d) for d in gb])
    return None if np.all(om) else om


def _zero_fill(data: jax.Array, mask: Optional[np.ndarray],
               bound_rank: int) -> jax.Array:
    if mask is None:
        return data
    m = jnp.asarray(mask.reshape(mask.shape + (1,) * bound_rank))
    return jnp.where(m, data, jnp.zeros((), data.dtype))


@dataclasses.dataclass(frozen=True)
class OperandLayout:
    """How the fused 2-D contraction lays out an input relation's payload
    as an operand of the Pallas kernel: keep the frontier ``keep`` of the
    key axes, transpose by ``perm``, reshape to ``shape2d`` and zero-pad to
    multiples of ``tile``, the kernel's blocks.  It names the operand:
    equal layouts of one buffer are one operand."""

    src_shape: Tuple[int, ...]
    dtype: str
    keep: Tuple[int, ...]
    perm: Tuple[int, ...]
    shape2d: Tuple[int, int]
    tile: Tuple[int, int]

    @property
    def copies(self) -> bool:
        """False where the operand is the source's own bytes reshaped: no
        slice, no pad, and no axis longer than one moves."""
        shape = self.keep + self.src_shape[len(self.keep):]
        if shape != self.src_shape or any(
                s % t for s, t in zip(self.shape2d, self.tile)):
            return True
        moved = [a for a in self.perm if shape[a] > 1]
        return moved != sorted(moved)

    def apply(self, data: jax.Array) -> jax.Array:
        from repro.kernels.matmul.ops import LAYOUT_SCOPE, pad_operand
        with jax.named_scope(LAYOUT_SCOPE):
            x = _to_2d(data[tuple(slice(0, k) for k in self.keep)],
                       self.perm, self.shape2d)
        return pad_operand(x, self.tile)


@functools.partial(jax.jit, static_argnums=0)
def lay_out(layout: OperandLayout, data: jax.Array) -> jax.Array:
    """``data`` as the operand ``layout`` describes, in one program."""
    return layout.apply(data)


@dataclasses.dataclass
class Prelaid(TensorRelation):
    """An input relation whose contraction operands were laid out before
    the program ran (the caller declared it stationary): ``operands`` by
    :class:`OperandLayout`.  A layout the contraction asks for and does
    not find is noted in ``missing`` and laid out in the program."""

    operands: Dict[OperandLayout, jax.Array] = dataclasses.field(
        default_factory=dict)
    missing: List[OperandLayout] = dataclasses.field(default_factory=list)

    def operand(self, layout: OperandLayout) -> Optional[jax.Array]:
        if not layout.copies:
            return None
        op = self.operands.get(layout)
        if op is None and layout not in self.missing:
            self.missing.append(layout)
        return op


def _to_2d(data: jax.Array, perm, shape2d) -> jax.Array:
    return jnp.transpose(data, perm).reshape(shape2d)


def _operand_2d(rel: TensorRelation, data: jax.Array, keep, src_perm,
                perm, shape2d, impl: str) -> jax.Array:
    """One operand of the 2-D contraction: ``data`` (``rel``'s payload cut
    to ``keep`` and transposed by ``src_perm``) transposed by ``perm`` to
    ``shape2d``, or the kernel's laid-out copy a :class:`Prelaid` input
    carries.  XLA's dot takes the operand from the program, where XLA may
    fold its layout into the product."""
    from repro.kernels.matmul.ops import LAYOUT_SCOPE, tile_multiples
    tile = tile_multiples(shape2d, impl)
    if isinstance(rel, Prelaid) and tile is not None:
        op = rel.operand(OperandLayout(
            tuple(rel.data.shape), str(rel.data.dtype), tuple(keep),
            tuple(src_perm[a] for a in perm), shape2d, tile))
        if op is not None:
            return op
    with jax.named_scope(LAYOUT_SCOPE):
        return _to_2d(data, perm, shape2d)


def _fused_matmul_2d(g: _JoinGeometry, left: TensorRelation,
                     right: TensorRelation, jkl: Tuple[int, ...],
                     gb: Tuple[int, ...], impl: str = "auto") -> jax.Array:
    """Collapse Σ∘⋈_(matMul→matAdd) into ONE blocked 2-D matmul.

    Valid when every joined key dim is reduced and every reduced dim is
    joined: the whole expression is exactly ``(I·m, K·c) @ (K·c, J·n)`` —
    the paper's claim that the TRA plan *is* the hand-tuned contraction.
    Dispatches through :func:`repro.kernels.matmul.ops.matmul`, which
    with ``impl="auto"`` selects the Pallas MXU kernel on TPU and the XLA
    matmul elsewhere.  A :class:`Prelaid` operand arrives already in the
    2-D layout, padded as the kernel pads it.
    """
    from repro.kernels.matmul.ops import LAYOUT_SCOPE, matmul as matmul_op

    kl = g.kl
    kept_l = [ax for ax in range(kl) if ax not in jkl]
    kept_r = [ax for ax in range(kl, g.k_out)]
    m, c = left.bound
    _, n = right.bound
    rows = math.prod(g.f_out_l[ax] for ax in kept_l) * m
    inner = math.prod(g.f_out_l[ax] for ax in jkl) * c
    cols = math.prod(g.out_key_shape[ax] for ax in kept_r) * n
    # the re-layouts run under the "tra.layout" scope, which names their
    # device ops in a profile
    # left: (f_out_l ++ (m, c)) → (kept_l..., m, joined..., c) → 2-D
    lperm = kept_l + [kl] + list(jkl) + [kl + 1]
    L2 = _operand_2d(left, g.ldata, g.f_out_l, tuple(range(kl + 2)),
                     lperm, (rows, inner), impl)
    # right: covered-axis order → (joined in jkl order..., c,
    # kept_r..., n)
    pos = {ax: i for i, ax in enumerate(g.covered)}
    nb = len(g.covered)
    rperm = [pos[ax] for ax in jkl] + [nb] \
        + [pos[ax] for ax in kept_r] + [nb + 1]
    R2 = _operand_2d(right, g.rdata_t, g.r_keep, g.r_order + (nb, nb + 1),
                     rperm, (inner, cols), impl)
    out2 = matmul_op(L2, R2, impl=impl, out_shape=(rows, cols))
    with jax.named_scope(LAYOUT_SCOPE):
        # back to blocks: (kept_l..., m, kept_r..., n) → gb order ++ (m, n)
        out = out2.reshape(
            tuple(g.f_out_l[ax] for ax in kept_l) + (m,)
            + tuple(g.out_key_shape[ax] for ax in kept_r) + (n,))
        axis_of = {ax: i for i, ax in enumerate(kept_l)}
        for j, ax in enumerate(kept_r):
            axis_of[ax] = len(kept_l) + 1 + j
        perm = [axis_of[d] for d in gb] + [len(kept_l),
                                           len(kept_l) + 1 + len(kept_r)]
        return jnp.transpose(out, perm)


def _fused_einsum(g: _JoinGeometry, left: TensorRelation,
                  right: TensorRelation, join_kernel: Kernel,
                  gb: Tuple[int, ...]) -> jax.Array:
    """Lower Σ∘⋈ to one ``jnp.einsum`` contraction (→ lax.dot_general)."""
    import string
    letters = string.ascii_lowercase + string.ascii_uppercase
    key_l = letters[:g.k_out]
    spec = _CONTRACTION_JOINS[join_kernel.name]
    if spec is None:                       # elementwise join kernel
        r = len(left.bound)
        bl = br = bo = letters[g.k_out:g.k_out + r]
    else:
        fresh = {ch: letters[g.k_out + i]
                 for i, ch in enumerate(sorted(set("".join(spec))))}
        bl, br, bo = ("".join(fresh[ch] for ch in part) for part in spec)
    l_sub = "".join(key_l[ax] for ax in range(g.kl)) + bl
    r_sub = "".join(key_l[ax] for ax in g.covered) + br
    o_sub = "".join(key_l[d] for d in gb) + bo
    ldata = _zero_fill(g.ldata, g.lmask, len(left.bound))
    rdata = _zero_fill(g.rdata_t, g.rmask_t, len(right.bound))
    return jnp.einsum(f"{l_sub},{r_sub}->{o_sub}", ldata, rdata)


def _fused_chunked(g: _JoinGeometry, left: TensorRelation,
                   right: TensorRelation, join_kernel: Kernel,
                   gb: Tuple[int, ...], reduce_dims: Tuple[int, ...],
                   agg_kernel: Kernel, chunk: int) -> jax.Array:
    """Stream the reduction over the contracted key dims.

    A ``fori_loop`` walks the flattened reduce-key grid ``chunk`` cells per
    step; each step materializes only ``chunk`` grid *slices* (one slice =
    the group-by grid × one reduce coordinate) and folds them into the
    accumulator with the associative agg kernel.  Peak live payload is
    O(output + chunk·slice) instead of the unfused O(full grid).
    """
    k_out, kl = g.k_out, g.kl
    out_bound = tuple(join_kernel.out_bound(left.bound, right.bound))
    ldata_b = g.ldata.reshape(g.f_out_l + (1,) * (k_out - kl)
                              + tuple(left.bound))
    rdata_b = g.rdata_t.reshape(g.r_shape + tuple(right.bound))
    jm = _joint_mask_grid(g)
    jm_dev = None if jm is None else jnp.asarray(jm)
    red_sizes = tuple(g.out_key_shape[d] for d in reduce_dims)
    nred = math.prod(red_sizes)

    def take(x, coords):
        for d, cidx in zip(reduce_dims, coords):
            cidx = jnp.minimum(cidx, x.shape[d] - 1)   # clamp size-1 axes
            x = jax.lax.dynamic_slice_in_dim(x, cidx, 1, axis=d)
        return x

    def cell_val(i):
        coords, rem = [], i
        for sz in reversed(red_sizes):
            coords.append(rem % sz)
            rem = rem // sz
        coords = coords[::-1]
        val = join_kernel.apply(take(ldata_b, coords), take(rdata_b, coords))
        if jm_dev is not None:
            msk = take(jm_dev, coords)
            fill = jnp.asarray(agg_kernel.identity, val.dtype)
            val = jnp.where(
                msk.reshape(msk.shape + (1,) * len(out_bound)), val, fill)
        return val

    csize = max(1, min(int(chunk), nred))
    while nred % csize:
        csize -= 1

    def step_val(s):
        base = s * csize
        if csize == 1:
            return cell_val(base)
        vals = jax.vmap(lambda j: cell_val(base + j))(jnp.arange(csize))
        return _tree_fold(vals, agg_kernel)

    acc = step_val(0)
    acc = jax.lax.fori_loop(
        1, nred // csize, lambda s, a: agg_kernel.apply(a, step_val(s)), acc)

    res = jnp.squeeze(acc, axis=reduce_dims)
    remaining = [d for d in range(k_out) if d not in reduce_dims]
    perm = [remaining.index(d) for d in gb] \
        + [len(gb) + i for i in range(len(out_bound))]
    return jnp.transpose(res, perm)


# Default streaming-chunk budget for the fused fallback path: each
# fori_loop step materializes ``chunk`` grid slices, so the bytes-based
# default keeps peak live payload near this budget regardless of shape.
DEFAULT_CHUNK_BYTES = 16 * 1024 * 1024


def fused_join_agg(left: TensorRelation, right: TensorRelation,
                   join_keys_l: Sequence[int], join_keys_r: Sequence[int],
                   join_kernel: Kernel, group_by: Sequence[int],
                   agg_kernel: Kernel, *,
                   chunk=None,
                   budget: Optional[int] = None,
                   ctx=None, node=None, spmd: bool = False) -> TensorRelation:
    """Σ_(groupBy, aggOp) ∘ ⋈_(jkl, jkr, projOp) without the grid.

    Semantically identical to ``agg(join(left, right, ...), group_by, ...)``
    (``group_by`` indexes the join's output key space) but lowered as:

    * one blocked 2-D matmul (Pallas on TPU) when (matMul, matAdd) collapses
      cleanly — the paper's BMM/CPMM/RMM inner contraction;
    * one ``jnp.einsum``/dot_general for any contraction-shaped pair
      (matMul / matTranMulL / matTranMulR / elemMul with matAdd);
    * a chunked ``lax.fori_loop`` streaming reduction for every other
      associative kernel pair.  ``chunk`` is the number of grid slices
      each loop step materializes; ``None`` derives it from
      :data:`DEFAULT_CHUNK_BYTES`, and ``"auto"`` (the Engine default)
      autotunes it from the device memory ``budget`` via the live-slice
      bytes model in :mod:`repro.store.autotune` (configurable per
      :class:`~repro.core.engine.Engine` via its ``chunk`` /
      ``memory_budget`` parameters).

    ``ctx`` (an :class:`~repro.core.guards.ExecContext`) hooks the fault
    injector's device-OOM model before each contraction lowers and, when
    ``ctx.stream`` is set (the engine's OOM degradation ladder), forces
    even contraction-shaped pairs onto the chunked streaming fallback so
    peak live memory is bounded by ``chunk`` slices.

    ``spmd=True`` marks a trace that XLA's SPMD partitioner will split
    across devices (the gspmd executor).  It cannot partition a Mosaic
    kernel, so the 2-D matmul then lowers to XLA's own dot.

    Falls back to the unfused pair when nothing is actually reduced or when
    holes cannot be identity-filled — the unfused path remains the
    correctness oracle in all cases.
    """
    jkl, jkr = tuple(join_keys_l), tuple(join_keys_r)
    gb = tuple(group_by)
    if not agg_kernel.is_associative:
        raise ValueError(f"agg kernel {agg_kernel.name} must be associative")
    g = _join_align(left, right, jkl, jkr)
    reduce_dims = tuple(d for d in range(g.k_out) if d not in gb)
    if not reduce_dims or not can_fuse(join_kernel, agg_kernel):
        return agg(join(left, right, jkl, jkr, join_kernel), gb, agg_kernel)

    out_bound = tuple(join_kernel.out_bound(left.bound, right.bound))
    out_key_shape = tuple(g.out_key_shape[d] for d in gb)
    out_mask = _fused_out_mask(g, gb, reduce_dims)

    # live-bytes estimates for the injected-OOM device model (ok_bytes):
    # inputs + output for the one-shot contraction; inputs + chunk slices
    # + accumulator/partial pair for the streamed fallback
    itemsize = jnp.dtype(left.data.dtype).itemsize
    out_floats = (math.prod(out_key_shape) if out_key_shape else 1) \
        * (math.prod(out_bound) if out_bound else 1)
    in_bytes = (g.ldata.size + g.rdata_t.size) * itemsize
    out_bytes = out_floats * itemsize

    streaming = ctx is not None and ctx.stream
    if (not streaming and agg_kernel.name == "matAdd"
            and join_kernel.name in _CONTRACTION_JOINS):
        if ctx is not None:
            ctx.on_contraction(stream=False, chunk=None, node=node,
                               bytes_live=in_bytes + out_bytes)
        if (join_kernel.name == "matMul" and g.lmask is None
                and g.rmask_t is None and set(reduce_dims) == set(jkl)):
            data = _fused_matmul_2d(g, left, right, jkl, gb,
                                    impl="jnp" if spmd else "auto")
        else:
            data = _fused_einsum(g, left, right, join_kernel, gb)
        return TensorRelation(
            data, RelType(out_key_shape, out_bound, data.dtype), out_mask)

    has_mask = g.lmask is not None or g.rmask_t is not None
    if has_mask and agg_kernel.identity is None:
        # cannot identity-fill holes — mirror tra.agg's requirement
        return agg(join(left, right, jkl, jkr, join_kernel), gb, agg_kernel)
    if chunk is None or chunk == "auto":
        slice_bytes = max(1, out_floats * itemsize)
        if chunk == "auto":
            from repro.store.autotune import chunk_slices
            chunk = chunk_slices(slice_bytes, out_bytes, budget)
        else:
            chunk = max(1, DEFAULT_CHUNK_BYTES // slice_bytes)
    if ctx is not None:
        ctx.on_contraction(
            stream=True, chunk=chunk, node=node,
            bytes_live=in_bytes + chunk * out_bytes + 2 * out_bytes)
    data = _fused_chunked(g, left, right, join_kernel, gb, reduce_dims,
                          agg_kernel, chunk)
    return TensorRelation(
        data, RelType(out_key_shape, out_bound, data.dtype), out_mask)


def rekey(rel: TensorRelation, key_func: KeyFunc,
          out_arity: Optional[int] = None) -> TensorRelation:
    """ReKey_(keyFunc)(R) — keys are static, so this is a static scatter."""
    keys = rel.valid_keys()
    new_keys = np.asarray([key_func(tuple(int(x) for x in k)) for k in keys],
                          dtype=np.int64)
    if new_keys.ndim == 1:
        new_keys = new_keys[:, None]
    if out_arity is not None and new_keys.shape[1] != out_arity:
        raise ValueError("key_func arity mismatch")
    if len(new_keys) == 0:
        raise ValueError("rekey of an empty relation")
    uniq = {tuple(k) for k in new_keys.tolist()}
    if len(uniq) != len(new_keys):
        raise ValueError("rekey produced duplicate keys (uniqueness violated)")
    f_out = tuple(int(m) + 1 for m in new_keys.max(axis=0))
    flat_src = np.ravel_multi_index(keys.T, rel.key_shape) if rel.key_shape \
        else np.zeros(1, np.int64)
    src = rel.data.reshape((-1,) + tuple(rel.bound))[flat_src]
    out = jnp.zeros(f_out + tuple(rel.bound), rel.data.dtype)
    out = out.at[tuple(new_keys.T)].set(src)
    mask = np.zeros(f_out, bool)
    mask[tuple(new_keys.T)] = True
    if np.all(mask):
        mask = None
    rt = RelType(f_out, rel.bound, rel.data.dtype)
    return TensorRelation(out, rt, mask)


def filt(rel: TensorRelation, bool_func: BoolFunc) -> TensorRelation:
    """σ_(boolFunc)(R) — static key predicate ⇒ static mask update."""
    grid = np.indices(rel.key_shape).reshape(rel.rtype.key_arity, -1).T
    keep = np.asarray([bool(bool_func(tuple(int(x) for x in k)))
                       for k in grid]).reshape(rel.key_shape)
    mask = keep if rel.mask is None else np.logical_and(rel.mask, keep)
    if not mask.any():
        raise ValueError("filter removed every tuple")
    # frontier shrink (paper §4.3 rule 3): slice to the bounding box
    idx = np.argwhere(mask)
    f_out = tuple(int(m) + 1 for m in idx.max(axis=0))
    sl = tuple(slice(0, f) for f in f_out)
    data = rel.data[sl]
    mask = mask[sl]
    if np.all(mask):
        mask = None
    rt = RelType(f_out, rel.bound, rel.data.dtype)
    return TensorRelation(data, rt, mask)


def pad(rel: TensorRelation, key_shape: Sequence[int]) -> TensorRelation:
    """Pad_(keyShape)(R) — densify: zero-fill holes, grow the frontier.

    The dual of σ, introduced for the autodiff layer: converts "tuple
    absent" into "tuple present with value 0" so cotangents over filtered
    key spaces can be accumulated on one common grid.
    """
    ks = tuple(key_shape)
    if len(ks) != rel.rtype.key_arity or \
            any(k < f for k, f in zip(ks, rel.key_shape)):
        raise ValueError(
            f"pad key_shape {ks} must cover frontier {rel.key_shape}")
    data = rel.data
    if rel.mask is not None:
        m = jnp.asarray(
            rel.mask.reshape(rel.mask.shape + (1,) * rel.rtype.rank))
        data = jnp.where(m, data, jnp.zeros((), data.dtype))
    if ks != rel.key_shape:
        widths = [(0, k - f) for k, f in zip(ks, rel.key_shape)] \
            + [(0, 0)] * rel.rtype.rank
        data = jnp.pad(data, widths)
    return TensorRelation(data, RelType(ks, rel.bound, data.dtype))


def transform(rel: TensorRelation, kernel: Kernel) -> TensorRelation:
    """λ_(transformFunc)(R)."""
    out = kernel.apply(rel.data)
    out_bound = tuple(kernel.out_bound(rel.bound))
    rt = RelType(rel.key_shape, out_bound, out.dtype)
    return TensorRelation(out, rt, rel.mask)


def tile(rel: TensorRelation, tile_dim: int, tile_size: int) -> TensorRelation:
    """Tile_(tileDim, tileSize)(R) — split an array dim, append a key dim."""
    b = rel.bound
    if b[tile_dim] % tile_size:
        raise ValueError("tile size must divide the bound")
    ntiles = b[tile_dim] // tile_size
    k = rel.rtype.key_arity
    ax = k + tile_dim
    shape = (rel.key_shape + b[:tile_dim] + (ntiles, tile_size)
             + b[tile_dim + 1:])
    x = rel.data.reshape(shape)
    x = jnp.moveaxis(x, ax, k)          # new key dim appended after keys
    new_bound = b[:tile_dim] + (tile_size,) + b[tile_dim + 1:]
    rt = RelType(rel.key_shape + (ntiles,), new_bound, rel.data.dtype)
    mask = None
    if rel.mask is not None:
        mask = np.repeat(rel.mask[..., None], ntiles, axis=-1)
    return TensorRelation(x, rt, mask)


def concat(rel: TensorRelation, key_dim: int, array_dim: int) -> TensorRelation:
    """Concat_(keyDim, arrayDim)(R) — inverse of tile."""
    if rel.mask is not None:
        mt = np.moveaxis(rel.mask, key_dim, -1)
        if not (np.all(mt == mt[..., :1])):
            raise ValueError("concat groups must be complete")
    k = rel.rtype.key_arity
    x = jnp.moveaxis(rel.data, key_dim, k - 1 + array_dim)
    # now the concat key dim sits immediately before the target array axis
    new_key_shape = tuple(s for d, s in enumerate(rel.key_shape)
                          if d != key_dim)
    nb = list(rel.bound)
    nb[array_dim] = rel.key_shape[key_dim] * rel.bound[array_dim]
    x = x.reshape(new_key_shape + tuple(nb))
    mask = None
    if rel.mask is not None:
        mask = np.take(rel.mask, 0, axis=key_dim)
        if np.all(mask):
            mask = None
    rt = RelType(new_key_shape, tuple(nb), rel.data.dtype)
    return TensorRelation(x, rt, mask)


# ==========================================================================
# Serving helpers (repro.serve): batch-key packing + fixed-capacity slots
# ==========================================================================
#
# A serving layer batches concurrent requests into ONE relation by adding a
# new leading key dim (the batch key), padded to a bucket size so the
# engine's structural compile cache stays hot across request counts.  The
# decode state lives in a fixed-capacity relation whose leading key dim
# indexes *slots*; admission/eviction are functional row writes.  All three
# helpers require continuous (mask-free) relations — serving padding is
# zero *rows*, not key holes, so the batched programs run on every staged
# executor (which reject masked inputs).

def _batched_rtype(rtype: RelType, bucket: int) -> RelType:
    return RelType((bucket,) + tuple(rtype.key_shape), tuple(rtype.bound),
                   rtype.dtype)


def pack_rows(rows: Sequence, bucket: int, rtype: RelType
              ) -> TensorRelation:
    """Pack per-request values into one bucket-padded batched relation.

    ``rows`` are :class:`TensorRelation`\\ s of type ``rtype`` (or raw
    dense arrays of shape ``key_shape ++ bound``), one per request.  The
    result gains a NEW leading key dim of size ``bucket`` — row ``i`` is
    request ``i``'s value; rows ``len(rows)..bucket-1`` are zero padding.
    Programs that never contract the batch key dim compute each row
    independently, so the padding rows are inert (see
    ``tests/test_serve.py`` for the masked-tail oracle).

    Host rows (anything that is neither a relation nor a device array)
    are written into one zero-padded host buffer that crosses to the
    device in a single transfer; rows already on the device are stacked
    there.
    """
    if not 0 < len(rows) <= bucket:
        raise ValueError(
            f"pack_rows: {len(rows)} rows do not fit bucket {bucket}")
    dense = tuple(rtype.key_shape) + tuple(rtype.bound)
    if not any(isinstance(r, (TensorRelation, jax.Array)) for r in rows):
        buf = np.zeros((bucket,) + dense, rtype.dtype)
        for i, r in enumerate(rows):
            arr = np.asarray(r, rtype.dtype)
            if arr.shape != dense:
                raise ValueError(
                    f"pack_rows: row {i} has dense shape {arr.shape}, "
                    f"expected {dense}")
            buf[i] = arr
        return TensorRelation(jax.device_put(buf),
                              _batched_rtype(rtype, bucket))
    datas = []
    for i, r in enumerate(rows):
        if isinstance(r, TensorRelation):
            if r.rtype.key_shape != rtype.key_shape \
                    or r.rtype.bound != rtype.bound:
                raise ValueError(
                    f"pack_rows: row {i} has type "
                    f"f={r.rtype.key_shape} b={r.rtype.bound}, expected "
                    f"f={rtype.key_shape} b={rtype.bound}")
            if r.mask is not None:
                raise ValueError(
                    f"pack_rows: row {i} carries a mask; serving "
                    f"relations must be continuous")
            datas.append(r.data)
        else:
            arr = jnp.asarray(r, rtype.dtype)
            if tuple(arr.shape) != dense:
                raise ValueError(
                    f"pack_rows: row {i} has dense shape "
                    f"{tuple(arr.shape)}, expected {dense}")
            datas.append(arr)
    stacked = jnp.stack(datas, axis=0)
    if len(rows) < bucket:
        padding = jnp.zeros((bucket - len(rows),) + dense, rtype.dtype)
        stacked = jnp.concatenate([stacked, padding], axis=0)
    return TensorRelation(stacked, _batched_rtype(rtype, bucket))


def unpack_rows(rel: TensorRelation, n: Optional[int] = None) -> list:
    """Split a batched relation back into per-request relations.

    Inverse of :func:`pack_rows` over the leading (batch) key dim:
    returns the first ``n`` rows (default: all) as relations typed
    without the batch key dim.
    """
    if rel.mask is not None:
        raise ValueError("unpack_rows: batched relations are continuous")
    if not rel.rtype.key_shape:
        raise ValueError("unpack_rows: relation has no batch key dim")
    bucket = rel.rtype.key_shape[0]
    n = bucket if n is None else n
    if not 0 <= n <= bucket:
        raise ValueError(f"unpack_rows: n={n} outside bucket {bucket}")
    row_rt = RelType(tuple(rel.rtype.key_shape[1:]),
                     tuple(rel.rtype.bound), rel.rtype.dtype)
    return [TensorRelation(rel.data[i], row_rt) for i in range(n)]


def scatter_rows(rel: TensorRelation, slots: Sequence[int],
                 rows: Sequence) -> TensorRelation:
    """Functionally write per-slot values into a fixed-capacity relation.

    ``rel`` is slot-keyed (leading key dim = capacity); ``rows[i]`` (a
    relation or dense array typed like one slot) replaces slot
    ``slots[i]``.  This is the serving layer's slot allocate/evict
    primitive: admission writes freshly initialized state rows, eviction
    zeroes freed ones — both out-of-place, so a compiled step program's
    inputs are never mutated under it.
    """
    if len(slots) != len(rows):
        raise ValueError(
            f"scatter_rows: {len(slots)} slots vs {len(rows)} rows")
    if rel.mask is not None:
        raise ValueError("scatter_rows: slot relations are continuous")
    if not rel.rtype.key_shape:
        raise ValueError("scatter_rows: relation has no slot key dim")
    if not slots:
        return rel
    capacity = rel.rtype.key_shape[0]
    dense = tuple(rel.rtype.key_shape[1:]) + tuple(rel.rtype.bound)
    datas = []
    for i, r in enumerate(rows):
        arr = r.data if isinstance(r, TensorRelation) else \
            jnp.asarray(r, rel.rtype.dtype)
        if tuple(arr.shape) != dense:
            raise ValueError(
                f"scatter_rows: row {i} has dense shape "
                f"{tuple(arr.shape)}, expected {dense}")
        datas.append(arr)
    idx = []
    for s in slots:
        if not 0 <= s < capacity:
            raise ValueError(
                f"scatter_rows: slot {s} outside capacity {capacity}")
        idx.append(int(s))
    if len(set(idx)) != len(idx):
        raise ValueError(f"scatter_rows: duplicate slots {idx}")
    data = rel.data.at[jnp.asarray(idx)].set(jnp.stack(datas, axis=0))
    return TensorRelation(data, rel.rtype)


def zero_rows(rel: TensorRelation, slots: Sequence[int]) -> TensorRelation:
    """Zero the given slots of a fixed-capacity relation (slot free).

    Implemented as a full-capacity mask multiply rather than a gather /
    scatter: the traced shapes depend only on the relation's type, never
    on ``len(slots)``, so a serving loop freeing a different number of
    slots each tick reuses ONE compiled XLA computation instead of
    paying a recompile per distinct eviction count.
    """
    if rel.mask is not None:
        raise ValueError("zero_rows: slot relations are continuous")
    if not rel.rtype.key_shape:
        raise ValueError("zero_rows: relation has no slot key dim")
    if not slots:
        return rel
    capacity = rel.rtype.key_shape[0]
    keep = np.ones((capacity,) + (1,) * (rel.data.ndim - 1),
                   dtype=np.asarray(rel.data).dtype)
    for s in slots:
        if not 0 <= s < capacity:
            raise ValueError(f"zero_rows: slot {s} out of range "
                             f"[0, {capacity})")
        keep[s] = 0.0
    return TensorRelation(rel.data * jnp.asarray(keep), rel.rtype)
