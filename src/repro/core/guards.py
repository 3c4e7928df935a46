"""Numeric guards with plan provenance + the executor run context.

Two related pieces:

* :class:`NumericsError` / the finite-checking machinery behind
  ``Engine(check_numerics=True)``.  The *first* checked node (in plan
  postorder, so producers are checked before consumers) whose output
  contains a NaN/Inf is named in the error — ``NumericsError:
  non-finite values first produced by node 7:TraTransform[log] ...`` —
  which turns "the loss is NaN" into "this kernel diverged".  On the
  ``reference`` executor every (non-structural, see
  :func:`node_needs_check`) node's output gets an eager mask-aware
  finite check.  On ``jit`` the guard is **two-tier** so it stays cheap
  enough to leave on in production: the steady-state program carries
  only *output-level* finite flags (one extra bool sync per dispatch;
  any non-finite intermediate either propagates to an output or is an
  output), and when a flag trips the engine lazily compiles an
  every-node-flagged variant of the same program and re-runs the same
  inputs once — deterministic, so the failure reproduces — to attribute
  the exact first producing node.  ``check_numerics="all"`` puts the
  per-node flags in the primary program instead (every dispatch pays
  the full flag traffic; useful when re-execution is undesirable).  On
  the distributed executors (``gspmd``/``shard_map``) the check wraps
  the executor *outputs* (per root), since per-node probes would
  perturb the collective schedule being tested.

* :class:`ExecContext` — the small per-compile context the
  :class:`~repro.core.engine.Engine` threads through all four executors.
  It carries the fault injector (:mod:`repro.core.faults`), the
  ``check_numerics`` flag machinery, the node-id/label table
  (:func:`label_nodes`, numbering identical to
  :func:`repro.core.engine.plan_sig`), and the ``stream`` flag of the
  OOM degradation ladder (force the fused Σ∘⋈ onto the chunked streaming
  fallback).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp


class NumericsError(RuntimeError):
    """A NaN/Inf was produced, attributed to a plan node when possible."""

    def __init__(self, msg: str, node_label: Optional[str] = None):
        super().__init__(msg)
        self.node_label = node_label


def _node_desc(n) -> str:
    """Human-readable node label body (kernel / name detail)."""
    from repro.core import plan as P
    t = type(n).__name__
    if isinstance(n, (P.TraInput, P.IAInput)):
        return f"{t}[{n.name}]"
    if isinstance(n, (P.TraJoin, P.LocalJoin)):
        return f"{t}[{n.kernel.name}]"
    if isinstance(n, P.FusedJoinAgg):
        return f"{t}[{n.join_kernel.name}→{n.agg_kernel.name}]"
    if isinstance(n, (P.TraAgg, P.LocalAgg)):
        return f"{t}[{n.kernel.name}]"
    if isinstance(n, (P.TraTransform, P.LocalMap)):
        return f"{t}[{n.kernel.name}]"
    return t


def label_nodes(roots) -> Dict[int, Tuple[int, str]]:
    """``id(node) -> (nid, label)`` over all roots, postorder, deduped.

    ``nid`` is the node's plan-signature id: the postorder index
    :func:`repro.core.engine.plan_sig` assigns (shared subexpressions
    numbered once; multi-root programs continue numbering across roots in
    root order, matching the tuple-of-signatures cache key).
    """
    from repro.core.plan import as_node, postorder
    out: Dict[int, Tuple[int, str]] = {}
    nid = 0
    for root in roots:
        for n in postorder(as_node(root)):
            if id(n) in out:
                continue
            out[id(n)] = (nid, f"{nid}:{_node_desc(n)}")
            nid += 1
    return out


def finite_flag(data: jax.Array, mask=None) -> Optional[jax.Array]:
    """Scalar bool: every (valid) entry finite.  None for exact dtypes."""
    import numpy as np
    if not jnp.issubdtype(data.dtype, jnp.inexact):
        return None
    if mask is not None and np.asarray(mask).all():
        mask = None                     # static all-ones mask: skip select
    if mask is not None:
        m = jnp.asarray(mask.reshape(mask.shape + (1,) * (data.ndim
                                                          - mask.ndim)))
        data = jnp.where(m, data, jnp.zeros((), data.dtype))
    return jnp.all(jnp.isfinite(data))


def node_needs_check(node, level=True) -> bool:
    """False for structural nodes that cannot *produce* a non-finite
    value from finite inputs (rekey/tile/pad/concat/filter and the IA
    data movements): skipping their flags keeps attribution on the first
    arithmetic producer while trimming guard traffic.  ``level="all"``
    checks every node.
    """
    from repro.core import plan as P
    if level == "all":
        return True
    return not isinstance(node, (P.TraReKey, P.TraTile, P.TraPad,
                                 P.TraConcat, P.TraFilter, P.LocalTile,
                                 P.LocalPad, P.LocalConcat, P.LocalFilter,
                                 P.Bcast, P.Shuf))


def _is_traced(x) -> bool:
    return isinstance(x, jax.core.Tracer)


@dataclasses.dataclass
class ExecContext:
    """Per-compile execution context threaded through the executor walks.

    ``on_node`` is called by the interpreters after each plan node's
    value is computed; it applies node-scoped injected faults and the
    per-node finite check.  ``flags`` accumulates ``(label, traced
    flag)`` pairs during a staged (jit) trace — the engine returns them
    as extra program outputs and raises host-side on the first failure.
    """

    faults: Optional[object] = None          # FaultInjector
    check: object = False                    # False | True (pruned) | "all"
    stream: bool = False                     # force chunked fused streaming
    labels: Dict[int, Tuple[int, str]] = dataclasses.field(
        default_factory=dict)
    flags: List[Tuple[str, jax.Array]] = dataclasses.field(
        default_factory=list)

    @property
    def active(self) -> bool:
        return self.faults is not None or self.check or self.stream

    def ids_of(self, node) -> Tuple[int, str]:
        return self.labels.get(id(node), (-1, type(node).__name__))

    def on_node(self, node, rel):
        """Fault + numerics hook over a freshly computed TensorRelation."""
        nid, label = self.ids_of(node)
        data = rel.data
        if self.faults is not None:
            poisoned = self.faults.on_node(nid, label, data)
            if poisoned is not data:
                from repro.core.tra import TensorRelation
                rel = TensorRelation(poisoned, rel.rtype, rel.mask)
                data = poisoned
        if self.check and node_needs_check(node, self.check):
            flag = finite_flag(data, rel.mask)
            if flag is not None:
                if _is_traced(flag) or _is_traced(data):
                    self.flags.append((label, flag))
                elif not bool(flag):
                    raise NumericsError(
                        f"non-finite values first produced by node {label} "
                        f"(eager finite-check; plan postorder attribution)",
                        node_label=label)
        return rel

    def on_array(self, node, data):
        """Array-valued variant (shard_map local walk): faults only —
        per-node finite checks would add per-shard probes; the engine
        checks distributed-executor outputs instead."""
        if self.faults is None:
            return data
        nid, label = self.ids_of(node)
        return self.faults.on_node(nid, label, data)

    def on_contraction(self, *, stream: bool, chunk: Optional[int],
                       node=None, bytes_live: Optional[int] = None) -> None:
        if self.faults is None:
            return
        nid, label = (-1, "") if node is None else self.ids_of(node)
        self.faults.on_contraction(stream=stream, chunk=chunk, nid=nid,
                                   label=label, bytes_live=bytes_live)

    def take_flags(self) -> List[Tuple[str, jax.Array]]:
        flags, self.flags = list(self.flags), []
        return flags


def check_output_rel(rel, label: str) -> None:
    """Output-level finite check (distributed executors): eager raise."""
    flag = finite_flag(rel.data, rel.mask)
    if flag is not None and not bool(flag):
        raise NumericsError(
            f"non-finite values in executor output {label} (per-node "
            f"attribution is available on the reference/jit executors)",
            node_label=label)


def is_oom_error(exc: BaseException) -> bool:
    """True for injected DeviceOOM and real RESOURCE_EXHAUSTED failures."""
    from repro.core.faults import DeviceOOM
    if isinstance(exc, DeviceOOM):
        return True
    return ("RESOURCE_EXHAUSTED" in str(exc)
            or "Out of memory" in str(exc)
            or isinstance(exc, jax.errors.JaxRuntimeError)
            and "memory" in str(exc).lower())
