"""Deterministic fault injection for TRA execution (the fault model).

Parallel environments fail; the paper's claim that TRA programs are
"easily executed with high efficiency in a parallel or distributed
environment" is only credible if the *recovery* paths are testable.  This
module provides the harness: a :class:`FaultInjector` that
:class:`~repro.core.engine.Engine` threads through every executor so
simulated failures fire at deterministic, plan-addressable points:

* **site failures** (:class:`SimulatedFailure`) — a node/host dies.  Fire
  per *run* (``step`` selector: the N-th ``CompiledExpr.run`` of the
  engine's artifacts — how a mid-training kill is simulated) or per *plan
  node* (``node`` selector, see below).
* **device OOM** (:class:`DeviceOOM`) — the fused Σ∘⋈ contraction path
  exhausts device memory.  The spec succeeds only once the engine has
  degraded to the chunked streaming fallback with a small enough chunk
  (``ok_chunk``), which is exactly what the engine's halving backoff
  ladder does (``Engine(degrade=True)``).
* **compile failures** (:class:`CompileFailure`) — a distributed executor
  cannot build its artifact; exercises the ``shard_map/gspmd → jit →
  reference`` fallback ladder.
* **stragglers** — a plan node (or whole run) is delayed by ``delay``
  seconds; lets timeout/monitoring machinery be tested without real slow
  hosts.
* **numeric faults** — a plan node's output is poisoned with NaN, so the
  ``check_numerics`` provenance machinery (:mod:`repro.core.guards`) can
  be shown to attribute the *first* non-finite value to the exact node.

**Node addressing.**  Node-scoped faults are keyed on *plan-signature
node ids*: the postorder index a node gets in
:func:`repro.core.engine.plan_sig` (shared subexpressions appear once).
``node`` may be that integer id or a substring of the node's label
(``"7:FusedJoinAgg[matMul→matAdd]"``); labels for a compiled artifact
come from :func:`repro.core.guards.label_nodes`.

**Timing caveat (documented, load-bearing).**  On the eager ``reference``
executor node hooks fire on *every run*, so ``step``-scoped node faults
behave per-step.  On the staged executors (``jit``/``gspmd``/
``shard_map``) node hooks fire at *trace* time — once per compile — so a
node-scoped fault there is baked into (or raised out of) the compile;
run-scoped faults (``step=`` with ``node=None``) fire on every executor
because they hook ``CompiledExpr.run`` itself.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Tuple, Union

import jax


class FaultError(RuntimeError):
    """Base class of all injected faults."""


class SimulatedFailure(FaultError):
    """A simulated site/node failure (the checkpoint/restart trigger).

    Canonical definition — :mod:`repro.runtime.trainer` re-exports it, so
    the dense trainer and the TRA trainer recover from the same fault
    type.
    """


class DeviceOOM(FaultError):
    """Simulated device out-of-memory in the fused contraction path."""


class CompileFailure(FaultError):
    """Simulated executor compile failure (degradation-ladder trigger)."""


#: The transient half of the taxonomy: failures a retry can clear because
#: they name a condition of the *attempt* (a site died, a device filled,
#: an executor's build flaked) rather than of the request.  Everything
#: else — type errors, bad payloads, shape mismatches — is permanent:
#: retrying replays the same deterministic rejection.
TRANSIENT_FAULTS = (SimulatedFailure, DeviceOOM, CompileFailure)


def is_transient(err: BaseException) -> bool:
    """Classify an execution failure against the fault taxonomy.

    True for the injected transient kinds (:data:`TRANSIENT_FAULTS`),
    for real runtime failures (:class:`jax.errors.JaxRuntimeError` —
    device resets, allocation failures), and for numeric-guard trips
    (:class:`repro.core.guards.NumericsError`): recomputation is
    deterministic, so a *persistent* poisoning exhausts any retry budget
    while injected/transient corruption clears on the next attempt.
    """
    if isinstance(err, TRANSIENT_FAULTS):
        return True
    if isinstance(err, jax.errors.JaxRuntimeError):
        return True
    try:
        from repro.core.guards import NumericsError
    except ImportError:      # pragma: no cover - guards is a sibling
        return False
    return isinstance(err, NumericsError)


@dataclasses.dataclass
class _Fault:
    kind: str                              # site | oom | compile | straggler | nan
    node: Union[int, str, None] = None     # plan-sig node id or label substring
    step: Optional[int] = None             # 0-based run index (on_run counter)
    every: Optional[int] = None            # periodic: fire when step % every == 0
    times: int = 1                         # remaining firings; -1 = unlimited
    delay: float = 0.0                     # straggler sleep seconds
    ok_chunk: int = 0                      # oom: succeed when streaming chunk <= this
    ok_bytes: Optional[int] = None         # oom: succeed when live bytes <= this
    executor: Optional[str] = None         # compile: executor that fails

    def matches_node(self, nid: int, label: str) -> bool:
        if isinstance(self.node, int):
            return self.node == nid
        if isinstance(self.node, str):
            return self.node in label
        return self.node is None

    def due_at(self, idx: int) -> bool:
        """Is this fault scheduled for run index ``idx``?

        ``step`` pins one run; ``every`` fires periodically (every N-th
        run, skipping run 0 so warm starts see at least one good tick).
        With neither selector a run-scoped fault never fires.
        """
        if self.step is not None:
            return self.step == idx
        if self.every is not None:
            return idx > 0 and idx % self.every == 0
        return False

    def spend(self) -> bool:
        """Consume one firing; False if the budget is exhausted."""
        if self.times == 0:
            return False
        if self.times > 0:
            self.times -= 1
        return True


class FaultInjector:
    """Scripted, deterministic fault source threaded through the Engine.

        inj = FaultInjector()
        inj.inject_site_failure(step=5)        # kill the 6th run
        eng = Engine(executor="jit", fault_injector=inj)

    Every fired fault is appended to ``self.log`` as a ``(kind, detail)``
    tuple so tests can assert exactly which recovery path executed.
    """

    def __init__(self) -> None:
        self._faults: List[_Fault] = []
        self.log: List[Tuple[str, str]] = []
        self.runs = 0                      # CompiledExpr.run invocations

    # -- scripting ---------------------------------------------------------
    def inject_site_failure(self, *, node=None, step: Optional[int] = None,
                            every: Optional[int] = None,
                            times: int = 1) -> "FaultInjector":
        """Kill one run (``step=``) or every N-th run (``every=``) — the
        periodic form is the chaos-harness schedule: a serving loop sees
        a site die on a fixed cadence and must keep its goodput SLO."""
        self._faults.append(_Fault("site", node=node, step=step,
                                   every=every, times=times))
        return self

    def inject_oom(self, *, node=None, ok_chunk: int = 1,
                   ok_bytes: Optional[int] = None,
                   times: int = -1) -> "FaultInjector":
        """OOM whenever the fused contraction runs unstreamed or with a
        streaming chunk larger than ``ok_chunk`` — models a fixed device
        memory budget, so the halving ladder deterministically bottoms
        out at the first rung that 'fits'.

        ``ok_bytes`` switches to the byte-accurate device model instead:
        the contraction fits iff its estimated live bytes (inputs +
        in-flight slices + output, as reported by the fused path) are
        under the budget.  This is the model the out-of-core tests use —
        an over-budget plan OOMs resident but fits once the host relation
        store streams its operands in key-range chunks."""
        self._faults.append(_Fault("oom", node=node, ok_chunk=ok_chunk,
                                   ok_bytes=ok_bytes, times=times))
        return self

    def inject_compile_failure(self, *, executor: str,
                               times: int = 1) -> "FaultInjector":
        self._faults.append(_Fault("compile", executor=executor,
                                   times=times))
        return self

    def inject_straggler(self, *, node=None, step: Optional[int] = None,
                         every: Optional[int] = None, delay: float = 0.05,
                         times: int = 1) -> "FaultInjector":
        self._faults.append(_Fault("straggler", node=node, step=step,
                                   every=every, delay=delay, times=times))
        return self

    def inject_nan(self, *, node, step: Optional[int] = None,
                   every: Optional[int] = None,
                   times: int = 1) -> "FaultInjector":
        """Poison a node's output with NaN — pinned to one run
        (``step=``), periodic (``every=``), or unconditional (neither).
        Periodic NaN only behaves per-run on the eager ``reference``
        executor (see the timing caveat in the module docstring)."""
        self._faults.append(_Fault("nan", node=node, step=step,
                                   every=every, times=times))
        return self

    # -- hooks (called by the Engine / executors) --------------------------
    def on_run(self) -> None:
        """Per ``CompiledExpr.run``; run-scoped site failures / stragglers."""
        idx = self.runs
        self.runs += 1
        for f in self._faults:
            if f.node is not None or not f.due_at(idx):
                continue
            if f.kind == "site" and f.spend():
                self.log.append(("site", f"run {idx}"))
                raise SimulatedFailure(f"injected site failure at run {idx}")
            if f.kind == "straggler" and f.spend():
                self.log.append(("straggler", f"run {idx} +{f.delay}s"))
                time.sleep(f.delay)

    def on_node(self, nid: int, label: str, data):
        """Per evaluated plan node.  May raise, sleep, or return a
        NaN-poisoned replacement for ``data`` (a jax array)."""
        out = data
        for f in self._faults:
            if f.node is None or not f.matches_node(nid, label):
                continue
            if (f.step is not None or f.every is not None) \
                    and not f.due_at(max(0, self.runs - 1)):
                continue
            if f.kind == "site" and f.spend():
                self.log.append(("site", label))
                raise SimulatedFailure(f"injected site failure at {label}")
            if f.kind == "straggler" and f.spend():
                self.log.append(("straggler", f"{label} +{f.delay}s"))
                time.sleep(f.delay)
            if f.kind == "nan" and f.spend():
                import jax.numpy as jnp
                self.log.append(("nan", label))
                if jnp.issubdtype(out.dtype, jnp.inexact):
                    out = out * jnp.asarray(float("nan"), out.dtype)
        return out

    def on_contraction(self, *, stream: bool, chunk: Optional[int],
                       nid: int = -1, label: str = "",
                       bytes_live: Optional[int] = None) -> None:
        """Inside the fused Σ∘⋈ path, before the contraction lowers."""
        for f in self._faults:
            if f.kind != "oom" or not f.matches_node(nid, label):
                continue
            if f.ok_bytes is not None:
                fits = bytes_live is not None and bytes_live <= f.ok_bytes
                limit = f"live bytes <= {f.ok_bytes}"
            else:
                fits = stream and chunk is not None and chunk <= f.ok_chunk
                limit = f"streaming chunk <= {f.ok_chunk}"
            if not fits and f.spend():
                mode = f"stream chunk={chunk}" if stream else "unstreamed"
                if bytes_live is not None:
                    mode += f" ~{bytes_live}B"
                self.log.append(("oom", f"{label or 'fused'} {mode}"))
                raise DeviceOOM(
                    f"injected device OOM in fused contraction ({mode}; "
                    f"fits only at {limit})")

    def on_compile(self, executor: str) -> None:
        """Before an executor builds its compiled artifact."""
        for f in self._faults:
            if f.kind == "compile" and f.executor == executor and f.spend():
                self.log.append(("compile", executor))
                raise CompileFailure(
                    f"injected compile failure on executor {executor!r}")
