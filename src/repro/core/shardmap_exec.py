"""Explicit-collective IA executor (paper-faithful `shard_map` mode).

Where the GSPMD executor *describes* placements and lets XLA choose the
collectives, this executor *is* the IA: every ``BCAST`` is a
``jax.lax.all_gather``, every ``SHUF`` an ``all_to_all`` (or a local slice /
gather, depending on source and target placements), and the two-phase
aggregation state (``dup_axes``) resolves through ``psum_scatter``
(reduce-scatter) or ``psum`` (all-reduce) — exactly the collective schedule
the paper's cost model prices.

Supported subset (documented): continuous relations (no masks — push filters
to the logical layer first), local joins / aggregations / kernel maps /
tiles / concats.  Key-rewriting maps require a replicated input.  This mode
is the semantics reference for the distributed algebra and runs in tests on
host-device meshes; the production models use the GSPMD mode.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.core import tra
from repro.core.interp import (_merge_ia_inputs, _placer, _pspec_for,
                               _warn_deprecated)
from repro.core.plan import (Bcast, FusedJoinAgg, IAConst, IAInput, IANode,
                             LocalAgg, LocalConcat, LocalFilter, LocalJoin,
                             LocalMap, LocalPad, LocalTile, Placement, Shuf,
                             TypeInfo, as_node, infer, postorder)
from repro.core.tra import RelType, TensorRelation


def _local_rtype(info: TypeInfo, mesh: Mesh) -> RelType:
    ks = list(info.rtype.key_shape)
    p = info.placement
    if p is not None and p.kind == "partitioned":
        for d, ax in zip(p.dims, p.axes):
            size = mesh.shape[ax]
            if ks[d] % size:
                raise ValueError(
                    f"frontier dim {d} ({ks[d]}) not divisible by axis "
                    f"{ax} ({size})")
            ks[d] //= size
    return RelType(tuple(ks), info.rtype.bound, info.rtype.dtype)


def _cross_site_reduce(x: jax.Array, ax: str, kernel_name: Optional[str]
                       ) -> jax.Array:
    """All-reduce the pending partials along mesh axis ``ax`` with the agg
    kernel's combiner — the psum-equivalent for every associative reducer.

    ``matAdd`` is ``psum``, ``elemMax``/``elemMin`` are ``pmax``/``pmin``;
    any other associative kernel (``elemMul`` → product, ``minIndex``, …)
    gathers the per-site partials and folds them locally — same wire
    volume as the ring all-reduce's gather phase, and semantically exact
    because aggregation kernels are associative by construction.
    """
    if kernel_name in (None, "matAdd"):
        return jax.lax.psum(x, ax)
    if kernel_name == "elemMax":
        return jax.lax.pmax(x, ax)
    if kernel_name == "elemMin":
        return jax.lax.pmin(x, ax)
    from repro.core.kernels_registry import get_kernel
    kern = get_kernel(kernel_name)
    if not kern.is_associative:
        raise NotImplementedError(
            f"shard_map two-phase aggregation for kernel {kernel_name}")
    stacked = jax.lax.all_gather(x, ax, axis=0, tiled=False)
    if kern.reduce is not None:
        return kern.reduce(stacked, (0,))
    return tra._tree_fold(stacked, kern)


def _resolve_dups(x: jax.Array, src: Placement, tgt: Optional[Placement],
                  mesh: Mesh) -> Tuple[jax.Array, Placement]:
    """Reduce pending duplicate-key partials (R2-5's second phase).

    Additive reducers scatter straight through ``psum_scatter``
    (reduce-scatter); other associative reducers all-reduce via
    :func:`_cross_site_reduce` and, when the target placement partitions a
    dim along the dup axis, slice their local window afterwards — the same
    final placement at the cost of the all-reduce's extra gather.
    """
    if not src.dup_axes:
        return x, src
    remaining_dups = list(src.dup_axes)
    scattered = []            # (dim, axis) pairs landing partitioned
    if tgt is not None and tgt.kind == "partitioned":
        for d, ax in zip(tgt.dims, tgt.axes):
            if ax in remaining_dups:
                size = mesh.shape[ax]
                if x.shape[d] % size == 0:
                    if src.dup_kernel in (None, "matAdd"):
                        # reduce-scatter: sum over ax, scatter along d
                        x = jax.lax.psum_scatter(
                            x, ax, scatter_dimension=d, tiled=True)
                    else:
                        x = _cross_site_reduce(x, ax, src.dup_kernel)
                        local = x.shape[d] // size
                        idx = jax.lax.axis_index(ax)
                        x = jax.lax.dynamic_slice_in_dim(
                            x, idx * local, local, axis=d)
                    scattered.append((d, ax))
                else:
                    # fall back to all-reduce; the caller's _move slices
                    x = _cross_site_reduce(x, ax, src.dup_kernel)
                remaining_dups.remove(ax)
    for ax in remaining_dups:
        x = _cross_site_reduce(x, ax, src.dup_kernel)
    dims = list(src.dims) + [d for d, _ in scattered]
    axes = list(src.axes) + [ax for _, ax in scattered]
    return x, Placement.partitioned(dims, axes)


def _move(x: jax.Array, src: Placement, tgt: Placement,
          mesh: Mesh) -> jax.Array:
    """Repartition local block ``x`` from ``src`` to ``tgt`` placement."""
    x, src = _resolve_dups(x, src, tgt, mesh)
    src_map = {ax: d for d, ax in zip(src.dims, src.axes)}
    tgt_map = {} if tgt.kind == "replicated" \
        else {ax: d for d, ax in zip(tgt.dims, tgt.axes)}
    for ax in sorted(set(src_map) | set(tgt_map)):
        od, nd = src_map.get(ax), tgt_map.get(ax)
        if od == nd:
            continue
        if od is None:                         # replicated → sharded: slice
            size = mesh.shape[ax]
            local = x.shape[nd] // size
            idx = jax.lax.axis_index(ax)
            x = jax.lax.dynamic_slice_in_dim(x, idx * local, local, axis=nd)
        elif nd is None:                       # sharded → replicated: gather
            x = jax.lax.all_gather(x, ax, axis=od, tiled=True)
        else:                                  # dim change: all_to_all
            x = jax.lax.all_to_all(x, ax, split_axis=nd, concat_axis=od,
                                   tiled=True)
    return x


def _build_shardmap(roots, mesh: Mesh, chunk=None,
                    budget: Optional[int] = None, ctx=None):
    """Build the explicit-collective callable ONCE for a tuple of physical
    roots.

    Returns ``(call, names, out_infos)``: ``call(env) -> tuple`` of global
    :class:`TensorRelation` results.  Building at *compile* time (instead
    of per ``run``) lets :class:`~repro.core.engine.Engine`'s structural
    compile cache reuse the constructed ``shard_map`` across run calls —
    repeat executions of one plan signature are pure dispatch.  Multiple
    roots execute inside one ``shard_map`` under a shared input
    environment (the multi-output path ``Engine.value_and_grad`` needs).

    ``ctx`` threads the engine's fault injector into the local walk
    (node-scoped faults fire at trace time here — see
    :mod:`repro.core.faults`); per-node numerics stay off inside the
    collective program, the engine checks the outputs instead.
    """
    roots = tuple(as_node(r) for r in roots)
    cache: Dict[int, TypeInfo] = {}
    out_infos = tuple(infer(r, cache=cache) for r in roots)
    by_name = _merge_ia_inputs(roots)
    names = sorted(by_name)
    for r in roots:
        for n in postorder(r):
            if cache[id(n)].mask is not None:
                raise NotImplementedError(
                    "shard_map mode requires continuous relations")

    def local_fn(*arrs):
        local_env = dict(zip(names, arrs))
        memo: Dict[int, jax.Array] = {}

        def rec(node) -> jax.Array:
            if id(node) in memo:
                return memo[id(node)]
            info = cache[id(node)]
            if isinstance(node, IAInput):
                out = local_env[node.name]
            elif isinstance(node, IAConst):
                lt = _local_rtype(info, mesh)
                out = jnp.full(tuple(lt.key_shape) + tuple(lt.bound),
                               node.fill, lt.dtype)
            elif isinstance(node, LocalPad):
                ct = cache[id(node.child)]
                cx = rec(node.child)
                if tuple(node.key_shape) == ct.rtype.key_shape:
                    out = cx        # masks are rejected above → identity
                else:
                    # frontier growth: placement rules force a replicated
                    # child, so the local block IS the global relation
                    crel = TensorRelation(cx, RelType(
                        cx.shape[:ct.rtype.key_arity], ct.rtype.bound,
                        ct.rtype.dtype))
                    out = tra.pad(crel, node.key_shape).data
            elif isinstance(node, (Bcast, Shuf)):
                child = rec(node.child)
                src = cache[id(node.child)].placement
                tgt = info.placement
                out = _move(child, src, tgt, mesh)
            elif isinstance(node, LocalJoin):
                lt, rt = cache[id(node.left)], cache[id(node.right)]
                lx, rx = rec(node.left), rec(node.right)
                lx, rx = _align_join_windows(node, lt, rt, lx, rx, mesh)
                lrel = TensorRelation(lx, RelType(
                    lx.shape[:lt.rtype.key_arity], lt.rtype.bound,
                    lt.rtype.dtype))
                rrel = TensorRelation(rx, RelType(
                    rx.shape[:rt.rtype.key_arity], rt.rtype.bound,
                    rt.rtype.dtype))
                out = tra.join(lrel, rrel, node.join_keys_l,
                               node.join_keys_r, node.kernel).data
            elif isinstance(node, LocalAgg):
                ct = cache[id(node.child)]
                cx = rec(node.child)
                crel = TensorRelation(cx, RelType(
                    cx.shape[:ct.rtype.key_arity], ct.rtype.bound,
                    ct.rtype.dtype))
                out = tra.agg(crel, node.group_by, node.kernel).data
            elif isinstance(node, FusedJoinAgg):
                # Σᴸ∘⋈ᴸ in one step over the local key windows.  For the
                # partial (R2-5) phase the per-site result carries pending
                # duplicates that the next Shuf/Bcast resolves through
                # psum_scatter / psum exactly as for LocalAgg.
                lt, rt = cache[id(node.left)], cache[id(node.right)]
                lx, rx = rec(node.left), rec(node.right)
                lx, rx = _align_join_windows(node, lt, rt, lx, rx, mesh)
                lrel = TensorRelation(lx, RelType(
                    lx.shape[:lt.rtype.key_arity], lt.rtype.bound,
                    lt.rtype.dtype))
                rrel = TensorRelation(rx, RelType(
                    rx.shape[:rt.rtype.key_arity], rt.rtype.bound,
                    rt.rtype.dtype))
                out = tra.fused_join_agg(
                    lrel, rrel, node.join_keys_l, node.join_keys_r,
                    node.join_kernel, node.group_by, node.agg_kernel,
                    chunk=chunk, budget=budget, ctx=ctx, node=node).data
            elif isinstance(node, LocalMap):
                ct = cache[id(node.child)]
                cx = rec(node.child)
                perm = None
                if node.key_func is not None and \
                        not ct.placement.is_replicated:
                    from repro.core.plan import _detect_key_permutation
                    perm = _detect_key_permutation(node.key_func,
                                                   ct.rtype.key_shape)
                    if perm is None:
                        raise NotImplementedError(
                            "non-permutation key rewrite on partitioned "
                            "data in shard_map mode")
                crel = TensorRelation(cx, RelType(
                    cx.shape[:ct.rtype.key_arity], ct.rtype.bound,
                    ct.rtype.dtype))
                if node.kernel.name != "idOp":
                    crel = tra.transform(crel, node.kernel)
                if node.key_func is not None:
                    if perm is not None:
                        # pure key-axis permutation: local transpose
                        k = ct.rtype.key_arity
                        axes = list(perm) + list(range(k, crel.data.ndim))
                        crel = TensorRelation(
                            jnp.transpose(crel.data, axes),
                            RelType(tuple(crel.rtype.key_shape[p]
                                          for p in perm),
                                    crel.rtype.bound, crel.rtype.dtype))
                    else:
                        crel = tra.rekey(crel, node.key_func)
                out = crel.data
            elif isinstance(node, LocalTile):
                ct = cache[id(node.child)]
                cx = rec(node.child)
                crel = TensorRelation(cx, RelType(
                    cx.shape[:ct.rtype.key_arity], ct.rtype.bound,
                    ct.rtype.dtype))
                out = tra.tile(crel, node.tile_dim, node.tile_size).data
            elif isinstance(node, LocalConcat):
                ct = cache[id(node.child)]
                cx = rec(node.child)
                crel = TensorRelation(cx, RelType(
                    cx.shape[:ct.rtype.key_arity], ct.rtype.bound,
                    ct.rtype.dtype))
                out = tra.concat(crel, node.key_dim, node.array_dim).data
            elif isinstance(node, LocalFilter):
                raise NotImplementedError("filter in shard_map mode")
            else:
                raise TypeError(type(node))
            if ctx is not None and ctx.faults is not None:
                out = ctx.on_array(node, out)
            memo[id(node)] = out
            return out

        outs = []
        for root, oi in zip(roots, out_infos):
            res = rec(root)
            # resolve any trailing duplicate state so the output is clean
            p = oi.placement
            if p is not None and p.dup_axes:
                res, _ = _resolve_dups(res, p, None, mesh)
            outs.append(res)
        return tuple(outs)

    in_specs = tuple(_pspec_for(by_name[n].placement, by_name[n].rtype)
                     for n in names)
    out_specs = []
    for oi in out_infos:
        out_p = oi.placement
        if out_p is not None and out_p.dup_axes:
            out_p = Placement.partitioned(out_p.dims, out_p.axes)
        out_specs.append(_pspec_for(out_p, oi.rtype))
    # jit the whole shard_map so repeat runs of a cached artifact are a
    # single XLA dispatch — without it every call re-traces the explicit
    # collective program eagerly, which dwarfs the kernel time for
    # multi-root programs (the train-step loop runs one of these per
    # step).  Everything inside is static-shape jnp (masks are rejected
    # above), so jit is always legal here.
    fn = jax.jit(jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                               out_specs=tuple(out_specs)))
    # inputs arrive in their in_specs layout, so a train step's outputs
    # fed back as inputs do not retrace and recompile ``fn``
    place = _placer(tuple(NamedSharding(mesh, spec) for spec in in_specs))

    def call(env: Dict[str, TensorRelation]):
        outs = fn(*place(tuple(env[n].data for n in names)))
        return tuple(TensorRelation(o, oi.rtype)
                     for o, oi in zip(outs, out_infos))

    return call, names, out_infos


def _execute_shardmap(root: IANode, env: Dict[str, TensorRelation],
                      mesh: Mesh) -> TensorRelation:
    """One-shot single-root execution (builds the shard_map afresh — the
    Engine path builds once at compile time instead)."""
    call, _, _ = _build_shardmap((root,), mesh)
    return call(env)[0]


def execute_shardmap(root: IANode, env: Dict[str, TensorRelation],
                     mesh: Mesh) -> TensorRelation:
    """Deprecated shim — use ``Engine(mesh, executor="shard_map").run``."""
    _warn_deprecated("execute_shardmap",
                     'Engine(mesh, executor="shard_map").run')
    return _execute_shardmap(root, env, mesh)


def _align_join_windows(node, lt: TypeInfo, rt: TypeInfo,
                        lx: jax.Array, rx: jax.Array, mesh: Mesh):
    """Slice a replicated side down to the partitioned side's key window.

    Inside shard_map, a partitioned relation's local key indices are
    *local*; a replicated side still has global indices.  For every joined
    dim pair where exactly one side is sharded, the full side is sliced to
    the sharded side's window so local indices correspond.
    """
    lp, rp = lt.placement, rt.placement
    for dl, dr in zip(node.join_keys_l, node.join_keys_r):
        lax_name = None if lp is None or lp.kind != "partitioned" \
            else lp.axis_of_dim(dl)
        rax_name = None if rp is None or rp.kind != "partitioned" \
            else rp.axis_of_dim(dr)
        if lax_name is not None and rax_name is None:
            size = mesh.shape[lax_name]
            local = rx.shape[dr] // size
            idx = jax.lax.axis_index(lax_name)
            rx = jax.lax.dynamic_slice_in_dim(rx, idx * local, local,
                                              axis=dr)
        elif rax_name is not None and lax_name is None:
            size = mesh.shape[rax_name]
            local = lx.shape[dl] // size
            idx = jax.lax.axis_index(rax_name)
            lx = jax.lax.dynamic_slice_in_dim(lx, idx * local, local,
                                              axis=dl)
    return lx, rx
