"""Plan interpreters: logical TRA, local IA, and distributed GSPMD IA.

Three evaluation modes:

* ``_evaluate_tra``   — walk a logical plan with the dense eager ops.
* ``_evaluate_ia``    — walk a physical plan ignoring sites (semantics
  check: a valid IA plan must equal its TRA source after projecting away
  sites).
* ``_evaluate_ia(spmd=True)`` — production path.  The same walk, but every
  ``BCAST``/``SHUF``/input placement becomes a sharding constraint inside a
  single ``jit``; XLA emits the collective schedule that the placements
  dictate (all-gather for BCAST, all-to-all for SHUF, reduce-scatter /
  all-reduce for the two-phase-aggregation placements).

A fourth mode — explicit shard_map collectives — lives in
:mod:`repro.core.shardmap_exec`.

The public names ``evaluate_tra`` / ``evaluate_ia`` / ``jit_ia_plan`` are
**deprecated shims** over the internals: the supported entry points are
``Engine.run`` / ``Engine.compile`` in :mod:`repro.core.engine`, which add
the optimizer, the compile cache, and a uniform executor selection on top
of these walks.  The shims warn with ``stacklevel`` pointing at the caller,
so the CI deprecation gate (``-W error::DeprecationWarning`` filtered to
``repro.*``) proves nothing inside the library still routes through them
while oracle tests may keep calling them directly.
"""
from __future__ import annotations

import warnings
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import tra
from repro.core.plan import (Bcast, FusedJoinAgg, IAConst, IAInput, IANode,
                             LocalAgg, LocalConcat, LocalFilter, LocalJoin,
                             LocalMap, LocalPad, LocalTile, Placement, Shuf,
                             TraAgg, TraConcat, TraConst, TraFilter, TraInput,
                             TraJoin, TraNode, TraPad, TraReKey, TraTile,
                             TraTransform, as_node, children, infer,
                             postorder)
from repro.core.tra import TensorRelation


def _const_rel(rtype, fill: float) -> TensorRelation:
    shape = tuple(rtype.key_shape) + tuple(rtype.bound)
    return TensorRelation(jnp.full(shape, fill, rtype.dtype), rtype)


def _warn_deprecated(old: str, new: str) -> None:
    warnings.warn(f"repro.core.{old} is deprecated; use {new} "
                  f"(see repro.core.engine.Engine)",
                  DeprecationWarning, stacklevel=3)


def _evaluate_tra(node: TraNode, env: Dict[str, TensorRelation],
                  _cache: Optional[dict] = None,
                  fuse: bool = True,
                  chunk=None,
                  budget: Optional[int] = None,
                  ctx=None) -> TensorRelation:
    """Walk a logical plan with the dense eager ops.

    With ``fuse=True`` (default) every ``TraAgg(TraJoin(...))`` pair whose
    kernels admit it executes through :func:`tra.fused_join_agg` — the
    Σ∘⋈ contraction — instead of materializing the join grid.  Joins with
    more than one consumer are exempt (they are computed once and cached).
    Pass ``fuse=False`` to force the unfused pair (the correctness oracle).
    ``chunk`` forwards to the fused path's streaming reduction (``None`` =
    bytes-based default).  ``ctx`` is the engine's
    :class:`~repro.core.guards.ExecContext`; when active, every computed
    node value passes through ``ctx.on_node`` (fault injection + per-node
    finite checks with plan provenance).
    """
    node = as_node(node)
    cache = _cache if _cache is not None else {}
    shared: set = set()
    if fuse:
        counts: Dict[int, int] = {}
        for n in postorder(node):
            for c in children(n):
                counts[id(c)] = counts.get(id(c), 0) + 1
        shared = {i for i, k in counts.items() if k > 1}

    def rec(n):
        if id(n) in cache:
            return cache[id(n)]
        if isinstance(n, TraInput):
            out = env[n.name]
        elif isinstance(n, TraConst):
            out = _const_rel(n.rtype, n.fill)
        elif isinstance(n, TraPad):
            out = tra.pad(rec(n.child), n.key_shape)
        elif isinstance(n, TraJoin):
            out = tra.join(rec(n.left), rec(n.right),
                           n.join_keys_l, n.join_keys_r, n.kernel)
        elif isinstance(n, TraAgg):
            c = n.child
            if fuse and isinstance(c, TraJoin) and id(c) not in cache \
                    and id(c) not in shared \
                    and tra.can_fuse(c.kernel, n.kernel):
                out = tra.fused_join_agg(
                    rec(c.left), rec(c.right), c.join_keys_l,
                    c.join_keys_r, c.kernel, n.group_by, n.kernel,
                    chunk=chunk, budget=budget, ctx=ctx, node=n)
            else:
                out = tra.agg(rec(n.child), n.group_by, n.kernel)
        elif isinstance(n, TraReKey):
            out = tra.rekey(rec(n.child), n.key_func)
        elif isinstance(n, TraFilter):
            out = tra.filt(rec(n.child), n.bool_func)
        elif isinstance(n, TraTransform):
            out = tra.transform(rec(n.child), n.kernel)
        elif isinstance(n, TraTile):
            out = tra.tile(rec(n.child), n.tile_dim, n.tile_size)
        elif isinstance(n, TraConcat):
            out = tra.concat(rec(n.child), n.key_dim, n.array_dim)
        else:
            raise TypeError(type(n))
        if ctx is not None and ctx.active:
            out = ctx.on_node(n, out)
        cache[id(n)] = out
        return out

    return rec(node)


def evaluate_tra(node: TraNode, env: Dict[str, TensorRelation],
                 _cache: Optional[dict] = None,
                 fuse: bool = True) -> TensorRelation:
    """Deprecated shim — use ``Engine(executor="reference").run(expr, ...)``."""
    _warn_deprecated("evaluate_tra", 'Engine(executor="reference").run')
    return _evaluate_tra(node, env, _cache, fuse)


def _pspec_for(placement: Optional[Placement], rtype) -> P:
    """PartitionSpec over the dense layout ``key_shape + bound``."""
    if placement is None or placement.is_replicated:
        return P()
    entries = []
    for d in range(rtype.key_arity):
        ax = placement.axis_of_dim(d)
        entries.append(ax)
    entries += [None] * rtype.rank
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def _evaluate_ia(node: IANode, env: Dict[str, TensorRelation],
                 mesh: Optional[Mesh] = None,
                 spmd: bool = False,
                 _cache: Optional[dict] = None,
                 chunk=None,
                 budget: Optional[int] = None,
                 ctx=None) -> TensorRelation:
    """Evaluate a physical plan.

    With ``spmd=True`` (requires ``mesh``) every placement-bearing node gets
    a ``with_sharding_constraint`` so that, lowered under ``jit``, XLA
    produces exactly the data movement the IA plan prescribes.
    """
    node = as_node(node)
    cache = _cache if _cache is not None else {}
    if id(node) in cache:
        return cache[id(node)]

    def rec(n):
        return _evaluate_ia(n, env, mesh, spmd, cache, chunk, budget, ctx)

    def constrain(rel: TensorRelation, placement: Placement) -> TensorRelation:
        if not spmd or mesh is None or placement is None:
            return rel
        if placement.has_duplicates:
            # partial duplicates are a transient SPMD state; the pending
            # reduction materializes at the next SHUF/BCAST constraint
            return rel
        spec = _pspec_for(placement, rel.rtype)
        data = jax.lax.with_sharding_constraint(
            rel.data, NamedSharding(mesh, spec))
        return TensorRelation(data, rel.rtype, rel.mask)

    if isinstance(node, IAInput):
        out = constrain(env[node.name], node.placement)
    elif isinstance(node, IAConst):
        out = constrain(_const_rel(node.rtype, node.fill), node.placement)
    elif isinstance(node, LocalPad):
        out = tra.pad(rec(node.child), node.key_shape)
        out = constrain(out, infer(node).placement)
    elif isinstance(node, Bcast):
        out = constrain(rec(node.child), Placement.replicated())
    elif isinstance(node, Shuf):
        out = constrain(rec(node.child),
                        Placement.partitioned(node.part_dims, node.axes))
    elif isinstance(node, LocalJoin):
        out = tra.join(rec(node.left), rec(node.right),
                       node.join_keys_l, node.join_keys_r, node.kernel)
        ti = infer(node)
        out = constrain(out, ti.placement)
    elif isinstance(node, LocalAgg):
        out = tra.agg(rec(node.child), node.group_by, node.kernel)
        ti = infer(node)
        out = constrain(out, ti.placement)
    elif isinstance(node, FusedJoinAgg):
        out = tra.fused_join_agg(rec(node.left), rec(node.right),
                                 node.join_keys_l, node.join_keys_r,
                                 node.join_kernel, node.group_by,
                                 node.agg_kernel, chunk=chunk,
                                 budget=budget, ctx=ctx, node=node,
                                 spmd=spmd)
        ti = infer(node)
        out = constrain(out, ti.placement)
    elif isinstance(node, LocalFilter):
        out = tra.filt(rec(node.child), node.bool_func)
    elif isinstance(node, LocalMap):
        child = rec(node.child)
        if node.kernel.name != "idOp":
            child = tra.transform(child, node.kernel)
        if node.key_func is not None:
            child = tra.rekey(child, node.key_func)
        out = child
    elif isinstance(node, LocalTile):
        out = tra.tile(rec(node.child), node.tile_dim, node.tile_size)
    elif isinstance(node, LocalConcat):
        out = tra.concat(rec(node.child), node.key_dim, node.array_dim)
    else:
        raise TypeError(type(node))
    if ctx is not None and ctx.active:
        out = ctx.on_node(node, out)
    cache[id(node)] = out
    return out


def evaluate_ia(node: IANode, env: Dict[str, TensorRelation],
                mesh: Optional[Mesh] = None,
                spmd: bool = False,
                _cache: Optional[dict] = None) -> TensorRelation:
    """Deprecated shim — use ``Engine.run`` (``executor="reference"`` for
    the sites-ignoring walk, ``executor="gspmd"`` for the SPMD path)."""
    _warn_deprecated("evaluate_ia", "Engine.run")
    return _evaluate_ia(node, env, mesh, spmd, _cache)


def _jit_ia_plan(root: IANode, mesh: Mesh,
                 input_order: Optional[list] = None
                 ) -> Tuple[Callable, list]:
    """Build a jitted function ``(*arrays) -> array`` executing ``root``.

    Input arrays arrive in ``input_order`` (names); shardings follow the
    plan's input placements.  The returned callable is suitable for
    ``.lower().compile()`` dry-runs and for real execution.
    """
    root = as_node(root)
    inputs = [n for n in postorder(root) if isinstance(n, IAInput)]
    by_name = {n.name: n for n in inputs}
    names = input_order or sorted(by_name)

    def fn(*arrays):
        env = {}
        for name, arr in zip(names, arrays):
            node = by_name[name]
            env[name] = TensorRelation(arr, node.rtype)
        rel = _evaluate_ia(root, env, mesh=mesh, spmd=True)
        return rel.data

    in_shardings = tuple(
        NamedSharding(mesh, _pspec_for(by_name[n].placement, by_name[n].rtype))
        for n in names)
    return jax.jit(fn, in_shardings=in_shardings), names


def jit_ia_plan(root: IANode, mesh: Mesh,
                input_order: Optional[list] = None
                ) -> Tuple[Callable, list]:
    """Deprecated shim — use ``Engine(mesh, executor="gspmd").compile``."""
    _warn_deprecated("jit_ia_plan", 'Engine(mesh, executor="gspmd").compile')
    return _jit_ia_plan(root, mesh, input_order)


def _placer(shardings) -> Callable[[tuple], tuple]:
    """``place(arrays)`` lays each array out as its entry of
    ``shardings`` before a dispatch that demands those layouts.

    A train step's outputs come back laid out as the plan left them and
    are fed to the next step as inputs.  ``jax.device_put`` between two
    layouts of the same devices copies shard by shard through a slow
    path (0.8 s a step for speech-100k's W1 on four v5e chips); a jitted
    identity reshards on the devices.  Arrays on other devices, or on
    the host, still go through ``device_put``.
    """
    moves = [jax.jit(lambda x: x, out_shardings=s) for s in shardings]

    def place(arrays):
        out = []
        for a, s, move in zip(arrays, shardings, moves):
            have = getattr(a, "sharding", None)
            if have is not None and have.is_equivalent_to(s, a.ndim):
                out.append(a)
            elif have is not None and have.device_set == s.device_set:
                out.append(move(a))
            else:
                out.append(jax.device_put(a, s))
        return tuple(out)

    return place


def _merge_ia_inputs(roots) -> Dict[str, IAInput]:
    """name → IAInput over several physical roots; conflicting declarations
    (type or placement) for one name are rejected."""
    by_name: Dict[str, IAInput] = {}
    for root in roots:
        for n in postorder(as_node(root)):
            if isinstance(n, IAInput):
                prev = by_name.get(n.name)
                if prev is not None and (prev.rtype != n.rtype
                                         or prev.placement != n.placement):
                    raise ValueError(
                        f"input {n.name!r} declared with conflicting "
                        f"type/placement across roots: "
                        f"{prev.placement.describe()} vs "
                        f"{n.placement.describe()}")
                by_name[n.name] = n
    return by_name


def _jit_ia_plans(roots, mesh: Mesh,
                  chunk=None,
                  budget: Optional[int] = None,
                  ctx=None) -> Tuple[Callable, list, tuple]:
    """Multi-root variant of :func:`_jit_ia_plan`: one jitted function
    ``(*arrays) -> tuple(arrays)`` executing every physical root under the
    shared SPMD input environment (required by ``Engine.value_and_grad``
    tuples on the GSPMD executor).  Also returns the input shardings the
    function demands, in ``names`` order."""
    roots = tuple(as_node(r) for r in roots)
    by_name = _merge_ia_inputs(roots)
    names = sorted(by_name)

    def fn(*arrays):
        env = {}
        for name, arr in zip(names, arrays):
            env[name] = TensorRelation(arr, by_name[name].rtype)
        cache: dict = {}
        return tuple(
            _evaluate_ia(r, env, mesh=mesh, spmd=True, _cache=cache,
                         chunk=chunk, budget=budget, ctx=ctx).data
            for r in roots)

    in_shardings = tuple(
        NamedSharding(mesh, _pspec_for(by_name[n].placement, by_name[n].rtype))
        for n in names)
    return jax.jit(fn, in_shardings=in_shardings), names, in_shardings
