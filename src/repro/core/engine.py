"""Unified evaluation entry point for the TRA: the :class:`Engine`.

One object owns everything between a logical expression and a result:

* the **optimizer invocation** (cost-based placement DP + logical rewrites,
  including the fused Σ∘⋈ contraction selection) with the engine's mesh
  topology and accounting mode;
* the **executor** choice — one declarative expression runs unchanged on
  any of the four back-ends:

  - ``"reference"`` — the eager sites-ignoring walk (logical plans run the
    dense eager ops; physical plans the semantics-check IA walk);
  - ``"jit"``       — the same walk staged into a single ``jax.jit``;
  - ``"gspmd"``     — one ``jit`` whose placement constraints make XLA emit
    the plan's collective schedule (requires ``mesh``);
  - ``"shard_map"`` — paper-faithful explicit collectives (requires
    ``mesh``);
  - ``"auto"``      — ``"gspmd"`` when a mesh is given, else ``"jit"``;

* a **keyed compile cache** — structurally identical expressions (same
  shapes, kernels, placements, executor) reuse the compiled artifact; and
* the **kernel registry view** (``engine.kernel(name)``).

The only two entry points are ``engine.run(expr, **inputs)`` and
``engine.compile(expr)``; everything else in :mod:`repro.core.interp` /
:mod:`repro.core.shardmap_exec` is a deprecated shim over the same
internals.

``run``/``compile`` accept an :class:`~repro.core.expr.Expr`, a raw
logical ``TraNode``, an already-built physical ``IANode`` (executed as-is,
bypassing the optimizer — how hand-compiled paper plans are priced and
run), a *tuple* of logical roots (multi-output programs such as the
§5.3 FFNN step; with ``optimize=False`` shared subexpressions are
evaluated once across all roots, while optimizer lowering rebuilds each
root's physical tree independently), or a *dict* of named roots — then
``run`` returns ``{name: relation}``, which is how
:class:`repro.core.train.TraTrainer` rethreads optimizer state between
steps.  Input values may be :class:`TensorRelation`\\ s or raw arrays of
the declared dense shape ``key_shape ++ bound``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import warnings
import weakref
from typing import (Callable, Dict, Mapping, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp

from repro.core import kernels_registry as kr
from repro.core.compile import compile_tra
from repro.core.interp import (_evaluate_ia, _evaluate_tra, _jit_ia_plans,
                               _placer)
from repro.core.optimize import OptimizeResult, optimize as _optimize
from repro.core.plan import (IAInput, IANode, Placement, TraInput, TraNode,
                             TypeInfo, as_node, describe, infer, postorder)
from repro.core.tra import OperandLayout, Prelaid, TensorRelation, lay_out
from repro.launch.metering import span

EXECUTORS = ("auto", "reference", "jit", "gspmd", "shard_map")

# graceful-degradation ladders (Engine(degrade=True)): on a *compile*
# failure of the preferred executor, fall back left-to-right; on a device
# OOM in the fused contraction at *run* time, retry streamed with a
# halving chunk starting here
_EXECUTOR_FALLBACKS = {
    "shard_map": ("jit", "reference"),
    "gspmd": ("jit", "reference"),
    "jit": ("reference",),
}
DEFAULT_OOM_LADDER_START = 64


def _is_compile_class(err: BaseException) -> bool:
    """Failures the executor fallback ladder may degrade past: injected
    compile failures, an executor's unsupported subset, runtime errors."""
    from repro.core.faults import CompileFailure
    return isinstance(err, (CompileFailure, NotImplementedError,
                            jax.errors.JaxRuntimeError))


def _validate_chunk(chunk) -> None:
    """``chunk`` is ``None``, ``"auto"`` or a positive int.

    The check itself lives with the other promoted input validation in
    :mod:`repro.analysis.inputs`; it raises the same ``ValueError`` (same
    leading text) as it always did, now carrying a rendered diagnostic.
    """
    from repro.analysis.inputs import check_chunk
    check_chunk(chunk)


VALIDATE_MODES = ("off", "warn", "strict")


# ==========================================================================
# Structural plan signatures (compile-cache keys)
# ==========================================================================

# id(fn)-only signatures have a fuzzer-found collision class: a kernel
# rebuilt after its predecessor was garbage-collected can reuse the exact
# id, and two kernels sharing one `apply` but differing in `out_bound`
# are distinct semantics under one id.  The content fingerprint below
# closes both; ids stay in the signature so live distinct objects never
# need a fingerprint comparison to separate.  Memoized per function
# *object* (weak keys — a GC'd function drops its entry, so a recycled id
# can never alias a stale fingerprint).
_code_fp_memo: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _code_fp(fn) -> str:
    """Content fingerprint of a callable (bytecode + consts + closure)."""
    try:
        return _code_fp_memo[fn]
    except (KeyError, TypeError):
        pass

    def feed(h, code):
        h.update(code.co_code)
        h.update(repr(code.co_names).encode())
        for c in code.co_consts:
            if hasattr(c, "co_code"):
                feed(h, c)              # nested lambdas/defs: hash content,
            else:                       # not their repr (which embeds ids)
                h.update(repr(c).encode())

    code = getattr(fn, "__code__", None)
    if code is None:
        # builtins / partials / callables: class + best-effort repr
        fp = f"{type(fn).__name__}:{getattr(fn, '__name__', repr(fn))}"
    else:
        h = hashlib.sha1()
        feed(h, code)
        for cell in getattr(fn, "__closure__", None) or ():
            try:
                h.update(repr(cell.cell_contents).encode())
            except Exception:
                h.update(b"?")
        fp = h.hexdigest()[:12]
    try:
        _code_fp_memo[fn] = fp
    except TypeError:
        pass                            # non-weakref-able callable
    return fp


def _kernel_sig(k) -> Tuple:
    # registered kernels are singletons and factory kernels embed their
    # parameters in the name (scaleMul(eta), einsum[...]); the id covers
    # ad-hoc kernels with colliding names, the content fingerprints cover
    # id reuse and shared-apply kernels (see _code_fp)
    return (k.name, id(k.apply), _code_fp(k.apply), _code_fp(k.out_bound))


def _func_sig(tag: str, fn) -> Tuple:
    # user key/bool functions are opaque — the tag plus identity keys them,
    # so structurally rebuilt expressions sharing the function object hit
    # the cache while different lambdas under a default tag never collide;
    # the fingerprint closes the id-reuse-after-GC collision
    return (tag, id(fn), _code_fp(fn))


def plan_sig(node) -> Tuple:
    """Structural signature of a logical or physical plan (cache key)."""
    node = as_node(node)
    memo: Dict[int, int] = {}
    parts = []

    def rec(n) -> int:
        if id(n) in memo:               # shared subexpression → back-ref
            return memo[id(n)]
        from repro.core import plan as P
        if isinstance(n, (P.TraInput, P.IAInput)):
            sig = ("in", n.name, n.rtype.key_shape, n.rtype.bound,
                   str(n.rtype.dtype))
            if isinstance(n, P.IAInput):
                # dup_kernel is semantics (which reduction the pending
                # R2-5 partials still owe) — a fuzzer-found collision
                # when it was absent
                sig += (n.placement.kind, n.placement.dims,
                        n.placement.axes, n.placement.dup_axes,
                        n.placement.dup_kernel)
        elif isinstance(n, (P.TraConst, P.IAConst)):
            sig = ("const", n.rtype.key_shape, n.rtype.bound,
                   str(n.rtype.dtype), n.fill)
            if isinstance(n, P.IAConst):
                sig += (n.placement.kind, n.placement.dims,
                        n.placement.axes, n.placement.dup_axes,
                        n.placement.dup_kernel)
        elif isinstance(n, (P.TraPad, P.LocalPad)):
            sig = ("pad", rec(n.child), n.key_shape)
        elif isinstance(n, (P.TraJoin, P.LocalJoin)):
            sig = ("join", rec(n.left), rec(n.right), n.join_keys_l,
                   n.join_keys_r, _kernel_sig(n.kernel))
        elif isinstance(n, P.FusedJoinAgg):
            sig = ("fja", rec(n.left), rec(n.right), n.join_keys_l,
                   n.join_keys_r, _kernel_sig(n.join_kernel), n.group_by,
                   _kernel_sig(n.agg_kernel), n.partial)
        elif isinstance(n, (P.TraAgg, P.LocalAgg)):
            sig = ("agg", rec(n.child), n.group_by, _kernel_sig(n.kernel),
                   getattr(n, "partial", False))
        elif isinstance(n, P.TraTransform):
            sig = ("map", rec(n.child), _kernel_sig(n.kernel))
        elif isinstance(n, P.LocalMap):
            sig = ("lmap", rec(n.child), _kernel_sig(n.kernel),
                   None if n.key_func is None
                   else _func_sig(n.tag, n.key_func))
        elif isinstance(n, (P.TraFilter, P.LocalFilter)):
            sig = ("filter", rec(n.child), _func_sig(n.tag, n.bool_func))
        elif isinstance(n, P.TraReKey):
            sig = ("rekey", rec(n.child), _func_sig(n.tag, n.key_func))
        elif isinstance(n, (P.TraTile, P.LocalTile)):
            sig = ("tile", rec(n.child), n.tile_dim, n.tile_size)
        elif isinstance(n, (P.TraConcat, P.LocalConcat)):
            sig = ("concat", rec(n.child), n.key_dim, n.array_dim)
        elif isinstance(n, P.Bcast):
            sig = ("bcast", rec(n.child))
        elif isinstance(n, P.Shuf):
            sig = ("shuf", rec(n.child), n.part_dims, n.axes)
        else:
            raise TypeError(type(n))
        memo[id(n)] = len(parts)
        parts.append(sig)
        return memo[id(n)]

    rec(node)
    return tuple(parts)


def _placements_sig(placements: Optional[Dict[str, Placement]]) -> Tuple:
    if not placements:
        return ()
    return tuple(sorted(
        (name, p.kind, p.dims, p.axes, p.dup_axes, p.dup_kernel or "")
        for name, p in placements.items()))


# ==========================================================================
# Compiled artifacts
# ==========================================================================

@dataclasses.dataclass
class CompiledExpr:
    """A compiled expression: physical plan (when one exists) + callable.

    ``__call__``/``run`` accept the program inputs by name and return
    :class:`TensorRelation` results (a tuple for multi-root programs).
    """

    executor: str
    roots: Tuple                        # plan nodes (logical or physical)
    input_rtypes: Dict[str, object]
    out_infos: Tuple[TypeInfo, ...]
    _call: Callable                     # env dict -> tuple of TensorRelation
    opts: Tuple[OptimizeResult, ...] = ()   # one per optimizer-lowered root
    multi: bool = False                 # caller passed a tuple of roots
    # jit/gspmd: the underlying jitted callable and its input-name order,
    # for .lower()/.compile() dry-runs, memory analysis and HLO inspection
    jitted: Optional[Callable] = None
    input_names: Optional[Tuple[str, ...]] = None
    # set by Engine.value_and_grad: names of the wrt inputs whose gradients
    # follow the value in the run() tuple
    grad_wrt: Optional[Tuple[str, ...]] = None
    # set for dict-compiled programs: run() returns {name: relation}
    root_names: Optional[Tuple[str, ...]] = None
    # the engine's FaultInjector (run-scoped faults hook every dispatch)
    faults: Optional[object] = None
    # set when Engine(degrade=True) fell back from a failed preferred
    # executor — names that executor so callers can see the degradation
    degraded_from: Optional[str] = None
    # stable process-local id ("<executor>:<sig digest>") assigned by the
    # engine at compile time; serving layers report which artifact served
    # a request by this id (see Engine.cache_info)
    artifact_id: Optional[str] = None
    # out-of-core streamed artifacts (Engine(memory_budget=...)): inputs
    # may be host-resident HostRelations, and validation defers to the
    # per-chunk inner compiles
    streamed: bool = False
    stream_stats: Optional[object] = None   # metering.StreamStats
    # set once run() has returned: its first call traces and compiles
    # (or loads from JAX's persistent cache), and is recorded apart
    ran: bool = False
    # jit: the call that takes run()'s stationary inputs' contraction
    # operands from the engine's Prelayouts, (env, names) -> outputs;
    # None where the executor ignores the declaration
    prelaid_call: Optional[Callable] = None

    @property
    def plan(self):
        """The (first) root plan node this artifact executes."""
        return self.roots[0]

    @property
    def opt(self) -> Optional[OptimizeResult]:
        """The optimizer result (single optimized root only)."""
        return self.opts[0] if len(self.opts) == 1 else None

    @property
    def cost(self) -> Optional[int]:
        """Comm cost of the optimizer's plan(s) — summed over roots."""
        return sum(o.cost for o in self.opts) if self.opts else None

    def describe(self) -> str:
        return "\n".join(describe(r) for r in self.roots)

    def run(self, stationary: Optional[Mapping[str, object]] = None,
            **inputs) -> Union[TensorRelation, Tuple]:
        """Run on ``inputs`` by name.  ``stationary`` holds more inputs
        that the caller passes as the same buffers from call to call, such
        as a server's weights: on ``jit`` their contraction operands are
        laid out once and kept by the engine (:class:`Prelayouts`); the
        other executors take them as ordinary inputs."""
        with span("engine.dispatch" if self.ran else "engine.first_run"):
            outs = self._run(inputs, stationary or {})
        self.ran = True
        return outs

    def _run(self, inputs, stationary) -> Union[TensorRelation, Tuple]:
        if self.faults is not None:
            self.faults.on_run()
        inputs = {**inputs, **stationary}
        # failure paths raise through repro.analysis.inputs (uniform
        # diagnostics, legacy exception types/text); imports stay off the
        # happy path
        unknown = [n for n in inputs if n not in self.input_rtypes]
        if unknown:
            from repro.analysis.inputs import unexpected_inputs_error
            raise unexpected_inputs_error(unknown, self.input_rtypes)
        env = {name: _coerce(name, val, self.input_rtypes[name],
                             keep_host=self.streamed)
               for name, val in inputs.items()}
        missing = [n for n in self.input_rtypes if n not in env]
        if missing:
            from repro.analysis.inputs import missing_inputs_error
            raise missing_inputs_error(missing, self.input_rtypes)
        if self.executor != "reference" and not self.streamed:
            # staged executors rebuild relations from raw arrays inside
            # the compiled artifact, so an input-side static mask would be
            # silently dropped — only the eager reference walk threads
            # per-value masks through (plan-level masks from in-plan
            # filters are unaffected; they live in the inferred types)
            holey = [n for n, r in env.items() if r.mask is not None]
            if holey:
                from repro.analysis.inputs import masked_inputs_error
                raise masked_inputs_error(self.executor, holey)
        if stationary and self.prelaid_call is not None:
            outs = self.prelaid_call(env, frozenset(stationary))
        else:
            outs = self._call(env)
        if self.root_names is not None:
            return dict(zip(self.root_names, outs))
        return outs if self.multi else outs[0]

    __call__ = run


@dataclasses.dataclass
class _CacheSlot:
    """Internal compile-cache slot: artifact + per-entry accounting."""

    compiled: CompiledExpr
    hits: int = 0
    pinned: bool = False


@dataclasses.dataclass(frozen=True)
class CacheEntry:
    """One compile-cache entry as reported by :meth:`Engine.cache_info`.

    ``signature`` is the full structural cache key (plan signatures,
    executor, optimizer configuration, …); ``artifact_id`` is its short
    digest — the id a serving layer logs per request.  ``degraded`` marks
    artifacts cached by the ``Engine(degrade=True)`` executor-fallback
    ladder under the fallback executor's key.
    """

    artifact_id: str
    executor: str
    hits: int
    pinned: bool
    degraded: bool
    root_names: Optional[Tuple[str, ...]]
    signature: Tuple
    compiled: CompiledExpr
    # per-artifact out-of-core streaming counters
    # (repro.launch.metering.StreamStats) for artifacts compiled through
    # the host relation store; None for resident artifacts
    stream_stats: Optional[object] = None


class Prelayouts:
    """Laid-out contraction operands of the inputs callers declare
    stationary (``CompiledExpr.run(stationary=...)``): one copy per source
    buffer and :class:`~repro.core.tra.OperandLayout`, shared by every
    artifact of the engine.

    Each copy is made by the first dispatch that needs it, under the span
    ``engine.prelayout``.  An input name holds the buffer last passed under
    it alive; passing another buffer under the name drops the old one's
    copies, unless another name still passes it.
    """

    def __init__(self) -> None:
        self._sources: Dict[str, object] = {}
        self._copies: Dict[Tuple[int, OperandLayout], jax.Array] = {}
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._copies)

    @property
    def nbytes(self) -> int:
        return sum(c.nbytes for c in self._copies.values())

    def operand(self, name: str, source, layout: OperandLayout):
        old = self._sources.get(name)
        if old is not source:
            self._sources[name] = source
            if old is not None and all(s is not old
                                       for s in self._sources.values()):
                self._copies = {k: c for k, c in self._copies.items()
                                if k[0] != id(old)}
        key = (id(source), layout)
        copy = self._copies.get(key)
        if copy is not None:
            self.hits += 1
            return copy
        self.misses += 1
        with span("engine.prelayout"):
            copy = self._copies[key] = lay_out(layout, source)
        return copy


def _coerce(name: str, value, rtype, keep_host: bool = False):
    if isinstance(value, TensorRelation):
        return value
    if rtype is None:
        raise ValueError(f"unexpected input {name!r}")
    # host-resident handles from the relation store (duck-typed so the
    # core layer does not import repro.store): streamed artifacts keep
    # them host-side and slice per chunk; resident artifacts materialize
    if hasattr(value, "to_relation") and hasattr(value, "split_dim"):
        if value.rtype != rtype:
            raise ValueError(
                f"input {name!r}: host relation type {value.rtype} != "
                f"declared {rtype}")
        return value if keep_host else value.to_relation()
    expect = tuple(rtype.key_shape) + tuple(rtype.bound)
    if tuple(value.shape) != expect:
        raise ValueError(
            f"input {name!r}: dense shape {tuple(value.shape)} != "
            f"key_shape ++ bound {expect}")
    return TensorRelation(value, rtype)


def _input_nodes(roots) -> Dict[str, object]:
    """name -> rtype over all roots; duplicate names must agree."""
    rtypes: Dict[str, object] = {}
    for root in roots:
        for n in postorder(root):
            if isinstance(n, (TraInput, IAInput)):
                prev = rtypes.get(n.name)
                if prev is not None and prev != n.rtype:
                    raise ValueError(
                        f"input {n.name!r} declared with conflicting types "
                        f"{prev} vs {n.rtype}")
                rtypes[n.name] = n.rtype
    return rtypes


# ==========================================================================
# Engine
# ==========================================================================

class Engine:
    """Unified entry point: optimizer + executor + compile cache.

    Parameters
    ----------
    mesh:
        Optional :class:`jax.sharding.Mesh`.  Provides the site axes and
        sizes for the optimizer and is required by the distributed
        executors.
    executor:
        One of ``"auto" | "reference" | "jit" | "gspmd" | "shard_map"``.
    optimize:
        ``True`` (default) runs the cost-based optimizer on logical roots
        (fused Σ∘⋈ selection included).  ``False`` compiles the Table-1
        default plan for distributed executors and walks the logical tree
        directly on ``reference``/``jit``.
    fuse:
        Only meaningful with ``optimize=False`` on logical walks: forward
        the ``fuse`` flag of the eager evaluator (``False`` = the unfused
        correctness oracle).
    input_placements / site_axes / axis_sizes / accounting /
    try_logical_rewrites:
        Optimizer configuration, defaulted from ``mesh`` when given
        (1-site ``("sites",)`` otherwise).
    chunk:
        Grid slices materialized per step of the chunked fused-Σ∘⋈
        streaming reduction (the non-contraction kernel pairs).
        ``"auto"`` (default) autotunes a per-shape value from the device
        memory budget (``memory_budget`` when given, else the device's
        calibrated ``memory_stats`` limit, else the static
        :data:`repro.core.tra.DEFAULT_CHUNK_BYTES` — see
        :mod:`repro.store.autotune`); ``None`` keeps the static
        bytes-based default; an int pins it.  ``compile(..., chunk=...)``
        overrides per expression.
    memory_budget:
        Optional device live-bytes budget enabling the spill-aware
        out-of-core mode: at compile time the engine estimates each
        plan's peak live bytes (:func:`repro.core.cost.plan_peak_bytes`)
        and routes over-budget single-root logical plans through the
        host relation store (:mod:`repro.store`) — operands stream in
        key-range chunks with double-buffered H2D copies instead of
        materializing resident.  Plans under budget run exactly as
        without it.
    store:
        Optional :class:`repro.store.RelationStore` backing
        ``HostRelation`` inputs/outputs (one is created lazily when
        needed).  ``engine.store.put(name, rel)`` turns any relation
        into a host-resident handle accepted by ``run``.
    fault_injector:
        Optional :class:`repro.core.faults.FaultInjector` threaded into
        every executor — simulated site failures, device OOM, stragglers
        and NaN poisoning fire at deterministic plan-addressable points
        (see :mod:`repro.core.faults` for the executor-timing caveat).
    check_numerics:
        ``True`` adds finite checks; a NaN/Inf raises
        :class:`repro.core.guards.NumericsError` naming the first
        producing plan node on ``reference``/``jit`` and the failing
        output on the distributed executors.  On ``jit`` the guard is
        two-tier (cheap enough to leave on): the steady-state program
        flags outputs only, and a trip triggers one deterministic
        re-run through a lazily compiled every-node-flagged variant for
        exact attribution.  ``"all"`` puts per-node flags in the
        primary jit program instead (full flag traffic every dispatch;
        no re-execution on failure).
    degrade:
        ``True`` enables graceful degradation: a device OOM in the fused
        contraction retries through a halving streamed-``chunk`` backoff
        ladder, and a failed executor compile falls back ``shard_map/gspmd
        → jit → reference`` with one :class:`RuntimeWarning`.  Off by
        default — without it every failure propagates unchanged.
    validate:
        Static plan verification mode (:mod:`repro.analysis`): on every
        compile-cache miss the post-optimization plans run the verifier
        passes (placement/exchange soundness, collective consistency,
        out-of-core streamability, memory-model audit).  ``"warn"``
        (default) emits one :class:`RuntimeWarning` carrying the rendered
        error diagnostics; ``"strict"`` raises
        :class:`repro.analysis.PlanVerificationError` (a ``ValueError``)
        instead of handing the plan to the executor; ``"off"`` skips
        verification.  Defaults from the ``REPRO_VALIDATE`` environment
        variable when unset (CI lints the program corpus under
        ``strict``).  The last run's findings — errors or not — are kept
        on ``engine.last_diagnostics``.
    """

    def __init__(self, mesh=None, executor: str = "auto",
                 optimize: bool = True, *,
                 input_placements: Optional[Dict[str, Placement]] = None,
                 site_axes: Optional[Sequence[str]] = None,
                 axis_sizes: Optional[Dict[str, int]] = None,
                 accounting: str = "wire",
                 try_logical_rewrites: bool = True,
                 fuse: bool = True,
                 chunk: Union[int, str, None] = "auto",
                 memory_budget: Optional[int] = None,
                 store=None,
                 fault_injector=None,
                 check_numerics=False,
                 degrade: bool = False,
                 validate: Optional[str] = None):
        from repro.analysis.inputs import check_memory_budget
        if executor not in EXECUTORS:
            raise ValueError(
                f"unknown executor {executor!r}; choose from {EXECUTORS}")
        _validate_chunk(chunk)
        check_memory_budget(memory_budget)
        if validate is None:
            validate = os.environ.get("REPRO_VALIDATE", "warn")
        if validate not in VALIDATE_MODES:
            raise ValueError(
                f"unknown validate mode {validate!r}; "
                f"choose from {VALIDATE_MODES}")
        self.validate = validate
        # Diagnostics of the most recent verified compile (any severity)
        self.last_diagnostics = None
        self.mesh = mesh
        self.fault_injector = fault_injector
        self.check_numerics = check_numerics
        self.degrade = degrade
        self.executor = executor
        self.optimize = optimize
        self.fuse = fuse
        # grid slices per streamed fused-reduction step; "auto" autotunes
        # from the device budget, None derives the static bytes-based
        # default from tra.DEFAULT_CHUNK_BYTES
        self.chunk = chunk
        # out-of-core mode: device live-bytes budget + host relation store
        self.memory_budget = memory_budget
        self._store_obj = store
        self.accounting = accounting
        self.try_logical_rewrites = try_logical_rewrites
        self.input_placements = dict(input_placements or {})
        if site_axes is None:
            site_axes = tuple(mesh.axis_names) if mesh is not None \
                else ("sites",)
        self.site_axes = tuple(site_axes)
        if axis_sizes is None:
            axis_sizes = ({a: int(mesh.shape[a]) for a in self.site_axes}
                          if mesh is not None
                          else {a: 1 for a in self.site_axes})
        self.axis_sizes = dict(axis_sizes)
        self._cache: Dict[Tuple, _CacheSlot] = {}
        self.cache_hits = 0
        self.cache_misses = 0
        self.prelayouts = Prelayouts()

    # -- host relation store (out-of-core tier) ---------------------------
    @property
    def store(self):
        """The engine's :class:`repro.store.RelationStore` (lazy)."""
        if self._store_obj is None:
            from repro.store import RelationStore
            self._store_obj = RelationStore()
        return self._store_obj

    # -- compile-cache introspection --------------------------------------
    def cache_info(self) -> Tuple[CacheEntry, ...]:
        """Per-entry view of the compile cache, in insertion order.

        Each entry carries the structural ``signature`` (the full cache
        key), the resolved ``executor``, the per-entry ``hits`` count
        (``sum(e.hits for e in cache_info()) == engine.cache_hits``), the
        ``pinned`` flag, and the ``degraded`` marker for artifacts the
        degradation ladder cached under a fallback executor.  This is how
        :class:`repro.serve.TraServer` reports which artifact served a
        request and how tests assert steady-state serving is 100% cache
        hits.
        """
        out = []
        for key, slot in self._cache.items():
            out.append(CacheEntry(
                artifact_id=slot.compiled.artifact_id or "?",
                executor=slot.compiled.executor,
                hits=slot.hits,
                pinned=slot.pinned,
                degraded=key[-1] == "degraded",
                root_names=slot.compiled.root_names,
                signature=key,
                compiled=slot.compiled,
                stream_stats=getattr(slot.compiled, "stream_stats", None)))
        return tuple(out)

    def pin(self, compiled: CompiledExpr) -> CompiledExpr:
        """Pin a compiled artifact: ``cache_clear()`` keeps it by default.

        Long-lived serving artifacts are pinned so periodic cache hygiene
        (or an explicit ``cache_clear()``) never evicts the programs the
        request path dispatches to.
        """
        for slot in self._cache.values():
            if slot.compiled is compiled:
                slot.pinned = True
                return compiled
        raise ValueError(
            f"artifact {compiled.artifact_id!r} is not in this engine's "
            f"compile cache (compiled by another engine?)")

    def cache_clear(self, *, pinned: bool = False) -> int:
        """Drop cache entries; ``pinned=True`` also drops pinned ones.

        Returns the number of entries evicted.  Hit/miss counters are
        cumulative and unaffected.
        """
        if pinned:
            n = len(self._cache)
            self._cache.clear()
            return n
        keep = {k: s for k, s in self._cache.items() if s.pinned}
        n = len(self._cache) - len(keep)
        self._cache = keep
        return n

    # -- kernel registry view ---------------------------------------------
    @staticmethod
    def kernel(name: str) -> kr.Kernel:
        return kr.get_kernel(name)

    @staticmethod
    def kernels() -> Sequence[str]:
        return kr.registered_kernels()

    # -- entry points ------------------------------------------------------
    def run(self, expr, **inputs) -> Union[TensorRelation, Tuple]:
        """Compile (with caching) and execute in one call.

        With ``memory_budget`` set (or ``HostRelation`` inputs) a
        single-root logical expression is first considered for the
        out-of-core path: when its estimated peak live bytes exceed the
        budget it executes through the host relation store, streaming
        key-range chunks with double-buffered transfers
        (:class:`repro.store.StreamExecutor`); under-budget plans run
        resident exactly as without the budget.

        With ``degrade=True`` a device OOM raised out of the fused
        contraction (injected :class:`~repro.core.faults.DeviceOOM` or a
        real ``RESOURCE_EXHAUSTED``) walks a two-rung recovery ladder:
        first the whole expression is retried *streamed through the host
        relation store* (out-of-core key-range chunking, which bounds
        peak operand bytes); if that cannot apply or still OOMs, the
        fused Σ∘⋈ is forced onto the chunked ``fori_loop`` fallback with
        a halving chunk ladder, trading arithmetic intensity for bounded
        peak memory until a rung fits.
        """
        from repro.core.guards import is_oom_error
        try:
            return self._dispatch(expr, inputs)
        except Exception as err:
            if not (self.degrade and is_oom_error(err)):
                raise
        # rung 1: out-of-core streaming through the relation store —
        # bounds peak device bytes without shrinking the fused chunk
        from repro.store.stream import NotStreamable
        try:
            warnings.warn(
                "device OOM in fused contraction; retrying streamed "
                "through the host relation store (out-of-core key-range "
                "chunks) before the last-resort chunked fallback",
                RuntimeWarning, stacklevel=2)
            return self._compile_streamed(expr, force=True).run(**inputs)
        except NotStreamable:
            pass
        except Exception as err:
            if not is_oom_error(err):
                raise
        # rung 2: force the fused Σ∘⋈ onto its chunked streaming fallback
        # with a halving chunk ladder
        start = self.chunk if isinstance(self.chunk, int) \
            else DEFAULT_OOM_LADDER_START
        warnings.warn(
            f"device OOM persists; degrading to the streamed chunked "
            f"fallback (halving chunk ladder from {start}) — consider a "
            f"smaller Engine(chunk=...), Engine(memory_budget=...), or "
            f"more device memory",
            RuntimeWarning, stacklevel=2)
        c = start
        while True:
            try:
                return self.compile(expr, chunk=c, _stream=True) \
                           .run(**inputs)
            except Exception as err:
                if not (is_oom_error(err) and c > 1):
                    raise
                c = max(1, c // 2)

    def _dispatch(self, expr, inputs):
        """Route a ``run`` through the out-of-core path when applicable."""
        if self._streaming_applicable(expr, inputs):
            from repro.store.stream import NotStreamable
            try:
                return self._compile_streamed(expr).run(**inputs)
            except NotStreamable:
                pass
        return self.compile(expr).run(**inputs)

    def _streaming_applicable(self, expr, inputs) -> bool:
        """Cheap pre-check: is the out-of-core path worth consulting?

        True when the engine has a memory budget or any input is a
        host-resident store handle.  Only single-root logical plans on
        the host executors stream; everything else runs resident.
        """
        if isinstance(expr, (dict, tuple, list)):
            return False
        if self._resolve_executor() not in ("reference", "jit"):
            return False
        has_host = any(hasattr(v, "to_relation") and
                       hasattr(v, "split_dim") for v in inputs.values())
        if not (has_host or self.memory_budget is not None):
            return False
        try:
            return isinstance(as_node(expr), TraNode)
        except TypeError:
            return False

    def _compile_streamed(self, expr, force: bool = False) -> CompiledExpr:
        """Compile ``expr`` as an out-of-core streamed artifact.

        Plans the expression through :class:`repro.store.StreamExecutor`
        (raising :class:`repro.store.NotStreamable` when the plan has no
        streamable axis, or — unless ``force`` — when it fits the budget
        resident) and caches a :class:`CompiledExpr` whose call runs the
        chunked double-buffered schedule.  ``force`` (the degradation
        ladder's rung-1 knob) streams even plans the estimator judges
        resident.
        """
        from repro.launch.metering import StreamStats
        from repro.store.stream import NotStreamable, StreamExecutor
        if isinstance(expr, (dict, tuple, list)):
            raise NotStreamable("multi-root programs run resident")
        root = as_node(expr)
        if not isinstance(root, TraNode):
            raise NotStreamable("physical IA plans run resident")
        if self._resolve_executor() not in ("reference", "jit"):
            raise NotStreamable(
                "out-of-core streaming chunks compile on the host "
                "executors (reference/jit) only")
        key = ("streamed", plan_sig(root), self._resolve_executor(),
               self.optimize, self.fuse, self.memory_budget, bool(force))
        hit = self._cache.get(key)
        if hit is not None:
            self.cache_hits += 1
            hit.hits += 1
            return hit.compiled
        se = StreamExecutor(self)
        try:
            splan = se.plan(root, force=force)   # may raise NotStreamable
        except NotStreamable as err:
            if self.validate == "off":
                raise
            # enrich the refusal with the static verifier's per-candidate
            # provenance diagnostics; the exception TYPE is preserved so
            # _dispatch's resident fallback (and callers catching
            # NotStreamable) behave exactly as before
            from repro.analysis.streaming import explain_unstreamable
            diags = explain_unstreamable(root, budget=self.memory_budget,
                                         fuse=self.fuse)
            self.last_diagnostics = diags
            if diags.errors:
                raise NotStreamable(
                    f"{err}\n{diags.render(min_severity='warning')}"
                ) from err
            raise
        self.cache_misses += 1
        stats = StreamStats(mode=splan.mode, budget_bytes=splan.budget)
        out_info = splan.out_info
        compiled = CompiledExpr(
            executor=f"{self._resolve_executor()}+stream",
            roots=(root,),
            input_rtypes=_input_nodes((root,)),
            out_infos=(out_info,),
            _call=lambda env: (se.execute(splan, env, stats),),
            streamed=True,
            stream_stats=stats)
        compiled.artifact_id = (
            f"{compiled.executor}:"
            f"{hashlib.sha1(repr(key).encode()).hexdigest()[:10]}")
        self._cache[key] = _CacheSlot(compiled)
        return compiled

    def compile(self, expr,
                input_placements: Optional[Dict[str, Placement]] = None,
                target: Optional[Placement] = None,
                chunk: Optional[int] = None,
                _grad_wrt: Optional[Tuple[str, ...]] = None,
                _stream: bool = False) -> CompiledExpr:
        """Compile an expression for this engine's executor.

        ``input_placements`` (falling back to the engine-level default)
        seed the optimizer; ``target`` constrains the result placement;
        ``chunk`` overrides the engine-level fused-path chunk size.
        ``_stream`` (the OOM ladder's knob) forces the fused Σ∘⋈ onto the
        chunked streaming fallback even for contraction kernel pairs.
        """
        with span("engine.lookup"):
            _validate_chunk(chunk)
            root_names = None
            if isinstance(expr, dict):
                # named multi-root program (train-step state threading):
                # run() returns {name: relation} so callers rethread
                # state-out → state-in by name
                root_names = tuple(expr)
                expr = tuple(expr.values())
            multi = isinstance(expr, (tuple, list))
            roots = tuple(as_node(e) for e in (expr if multi else (expr,)))
            placements = dict(self.input_placements)
            placements.update(input_placements or {})
            executor = self._resolve_executor()
            chunk = self.chunk if chunk is None else chunk

            # _grad_wrt is part of the key so a value_and_grad artifact
            # (which carries gradient semantics in .grad_wrt) never aliases
            # a plain compile() of the structurally identical roots; the
            # robustness fields (_stream / check_numerics / injector
            # identity) are keyed because they are baked into the compiled
            # callable
            inj = self.fault_injector
            key = (tuple(plan_sig(r) for r in roots), executor,
                   self.optimize, self.fuse, self.accounting,
                   self.try_logical_rewrites,
                   _placements_sig(placements),
                   _placements_sig({"·": target} if target else None),
                   multi, chunk, _grad_wrt, root_names,
                   _stream, self.check_numerics,
                   None if inj is None else id(inj))
            hit = self._cache.get(key)
        if hit is not None:
            self.cache_hits += 1
            hit.hits += 1
            return hit.compiled
        self.cache_misses += 1
        degraded_from = None
        try:
            with span("engine.plan"):
                compiled = self._compile(roots, placements, target,
                                         executor, multi, chunk,
                                         stream=_stream)
        except Exception as err:
            compiled, executor, err2 = self._compile_degraded(
                err, roots, placements, target, executor, multi, chunk,
                _stream)
            if compiled is None:
                raise err2
            degraded_from = self._resolve_executor()
            # the degraded artifact is cached under the *fallback*
            # executor's key (plus a marker): the preferred key stays
            # vacant, so the next compile() retries the preferred executor
            # and a later successful compile is never shadowed
            key = key[:1] + (executor,) + key[2:] + ("degraded",)
        compiled.grad_wrt = _grad_wrt
        compiled.root_names = root_names
        compiled.faults = inj
        compiled.degraded_from = degraded_from
        compiled.artifact_id = (
            f"{compiled.executor}:"
            f"{hashlib.sha1(repr(key).encode()).hexdigest()[:10]}")
        self._cache[key] = _CacheSlot(compiled)
        return compiled

    def _compile_degraded(self, err, roots, placements, target, executor,
                          multi, chunk, stream):
        """Walk the executor fallback ladder after a failed compile.

        Only *compile-class* failures degrade (injected
        :class:`~repro.core.faults.CompileFailure`, ``NotImplementedError``
        from an executor's unsupported subset, XLA runtime errors) — user
        errors such as shape/divisibility ``ValueError`` propagate
        unchanged.  Returns ``(compiled, executor, err)``; ``compiled`` is
        ``None`` when no rung succeeded (re-raise ``err``).
        """
        ladder = _EXECUTOR_FALLBACKS.get(executor, ())
        if not self.degrade or not ladder or not _is_compile_class(err):
            return None, executor, err
        for fb in ladder:
            try:
                compiled = self._compile(roots, placements, target, fb,
                                         multi, chunk, stream=stream)
            except Exception as err2:
                if not _is_compile_class(err2):
                    return None, executor, err2
                err = err2
                continue
            warnings.warn(
                f"executor {executor!r} failed to compile ({err}); "
                f"degraded to executor {fb!r} for this expression — fix "
                f"the {executor!r} failure to restore the preferred "
                f"executor (it is retried on the next compile)",
                RuntimeWarning, stacklevel=3)
            return compiled, fb, err
        return None, executor, err

    def value_and_grad(self, expr, wrt, seed=None,
                       input_placements: Optional[Dict[str,
                                                       Placement]] = None,
                       chunk: Optional[int] = None) -> CompiledExpr:
        """Compile ``(expr, *d expr/d wrt)`` as one multi-output program.

        The gradient expressions are *derived* from the forward plan by
        :mod:`repro.core.autodiff` (Tang et al. direction) and flow through
        the same optimizer/executor stack as any expression — the fused
        Σ∘⋈ selection applies to backward plans too.  ``wrt`` is a list of
        input names (or input ``Expr`` handles); ``seed`` is the output
        cotangent (default: ones — the gradient of the sum of every output
        entry).  The returned artifact's ``run`` yields
        ``(value, grad_0, grad_1, ...)`` in ``wrt`` order.
        """
        from repro.core.autodiff import grad as _grad
        from repro.core.expr import Expr, wrap
        if not isinstance(expr, Expr):
            expr = wrap(as_node(expr))
        wrt_list = list(wrt) if isinstance(wrt, (tuple, list)) else [wrt]
        grads = _grad(expr, wrt=wrt_list, seed=seed)
        names = tuple(w if isinstance(w, str) else w.node.name
                      for w in wrt_list)
        return self.compile((expr,) + tuple(grads),
                            input_placements=input_placements,
                            chunk=chunk, _grad_wrt=names)

    # -- internals ---------------------------------------------------------
    def _resolve_executor(self) -> str:
        if self.executor != "auto":
            return self.executor
        return "gspmd" if self.mesh is not None else "jit"

    def _physical_roots(self, roots, placements, target):
        """Lower logical roots to physical plans; pass IANodes through.

        Each logical root is optimized *independently* — physical lowering
        rebuilds nodes, so cross-root DAG sharing only survives on the
        unoptimized logical walk (``optimize=False``).  On the staged
        executors (jit/gspmd/shard_map) the duplicated lowering costs
        compile time only — XLA CSE merges the structurally identical
        subgraphs — and buys the fused Σ∘⋈ selection inside every root
        (the train-step programs rely on this); on the eager
        ``reference`` walk the duplicated roots re-execute per run, so
        shared-forward multi-root programs there should compile with
        ``optimize=False``.  ``CompiledExpr.cost`` sums the per-root
        plan costs.
        """
        phys, opts = [], []
        for r in roots:
            if isinstance(r, IANode):
                phys.append(r)
            elif self.optimize:
                opt = _optimize(
                    r, placements, site_axes=self.site_axes,
                    axis_sizes=self.axis_sizes, target=target,
                    try_logical_rewrites=self.try_logical_rewrites,
                    accounting=self.accounting)
                opts.append(opt)
                phys.append(opt.plan)
            else:
                phys.append(compile_tra(r, placements, self.site_axes))
        return tuple(phys), tuple(opts)

    def _make_ctx(self, plans, executor, stream):
        """Build the ExecContext threaded through the executor walks.

        ``None`` when no robustness feature is active — the walks then run
        exactly the pre-robustness code path.  Per-node finite checks run
        eagerly on ``reference``; ``jit`` collects per-node flags in the
        primary program only under ``check_numerics="all"`` (the default
        ``True`` mode is two-tier — output flags steady-state, per-node
        attribution on a lazily compiled re-run); the distributed
        executors get output-level checks (per-node probes would perturb
        the collective schedule under test).
        """
        from repro.core.guards import ExecContext, label_nodes
        if executor == "reference":
            per_node = self.check_numerics
        elif executor == "jit":
            # default jit mode flags outputs only (two-tier: the
            # per-node attribution variant is compiled lazily on a trip)
            per_node = "all" if self.check_numerics == "all" else False
        else:
            per_node = False
        if self.fault_injector is None and not per_node and not stream:
            return None
        return ExecContext(faults=self.fault_injector, check=per_node,
                           stream=stream, labels=label_nodes(plans))

    @staticmethod
    def _checked_call(call):
        """Wrap a distributed executor's call with output finite checks."""
        from repro.core.guards import check_output_rel
        def wrapped(env):
            outs = call(env)
            for i, r in enumerate(outs):
                check_output_rel(r, f"output[{i}]")
            return outs
        return wrapped

    def _verify_compile(self, plans, executor, logical_roots) -> None:
        """Run the static verifier over the executor-bound plans.

        Called once per compile-cache miss (cache hits re-dispatch
        already-verified artifacts).  ``"warn"`` surfaces error
        diagnostics as one RuntimeWarning; ``"strict"`` raises
        :class:`~repro.analysis.PlanVerificationError` before any
        executor construction.  All findings (any severity) are kept on
        ``self.last_diagnostics``.
        """
        if self.validate == "off":
            return
        from repro.analysis.diagnostics import PlanVerificationError
        from repro.analysis.manager import verify_plans
        diags = verify_plans(
            plans, executor=executor, axis_sizes=self.axis_sizes,
            memory_budget=self.memory_budget, fuse=self.fuse,
            logical_roots=logical_roots)
        self.last_diagnostics = diags
        if not diags.errors:
            return
        if self.validate == "strict":
            raise PlanVerificationError(diags)
        warnings.warn(
            f"plan verification found {len(diags.errors)} error(s) "
            f"(Engine(validate=\"warn\") — compiling anyway):\n"
            f"{diags.render(min_severity='warning')}",
            RuntimeWarning, stacklevel=4)

    def _compile(self, roots, placements, target, executor, multi,
                 chunk, stream=False) -> CompiledExpr:
        if self.fault_injector is not None:
            self.fault_injector.on_compile(executor)
        if executor in ("gspmd", "shard_map"):
            if self.mesh is None:
                raise ValueError(f"executor {executor!r} requires a mesh")
            phys, opts = self._physical_roots(roots, placements, target)
            self._verify_compile(phys, executor, roots)
            ctx = self._make_ctx(phys, executor, stream)
            out_infos = tuple(infer(p) for p in phys)
            jfn = names = None
            if executor == "gspmd":
                call, jfn, names = self._gspmd_call(phys, out_infos, chunk,
                                                    ctx)
            else:
                # the shard_map callable is built ONCE here; repeat runs of
                # a cached artifact are pure dispatch (no rebuild)
                call = self._shardmap_call(phys, chunk, ctx)
            if self.check_numerics:
                call = self._checked_call(call)
            return CompiledExpr(executor, phys, _input_nodes(phys),
                                out_infos, call, opts, multi,
                                jitted=jfn, input_names=names)

        # reference / jit: logical roots run the eager TRA walk (optimized
        # ones run the physical walk); shared subexpressions are evaluated
        # once via the id-keyed cache shared across roots.
        if self.optimize or any(isinstance(r, IANode) for r in roots):
            plans, opts = self._physical_roots(roots, placements, target)
        else:
            plans, opts = roots, ()
        self._verify_compile(plans, executor, roots)
        ctx = self._make_ctx(plans, executor, stream)
        out_infos = tuple(infer(p) for p in plans)
        rtypes = _input_nodes(plans)

        def eval_all(env, ectx):
            cache: dict = {}
            outs = []
            for p in plans:
                if isinstance(p, IANode):
                    outs.append(_evaluate_ia(p, env, _cache=cache,
                                             chunk=chunk,
                                             budget=self.memory_budget,
                                             ctx=ectx))
                else:
                    outs.append(_evaluate_tra(p, env, cache,
                                              fuse=self.fuse, chunk=chunk,
                                              budget=self.memory_budget,
                                              ctx=ectx))
            return tuple(outs)

        if executor == "reference":
            return CompiledExpr("reference", plans, rtypes, out_infos,
                                lambda env: eval_all(env, ctx), opts,
                                multi)

        names = sorted(rtypes)
        check = self.check_numerics
        # Two-tier jit numerics guard.  Finite flags become extra
        # (scalar) jit outputs, led by a single combined all-finite
        # scalar: the happy path costs one host sync per dispatch.  In
        # the default ``check_numerics=True`` mode the steady-state
        # program flags *outputs only* (cheap — no per-node reduce
        # traffic, no fusion breakage); when the combined flag trips,
        # ``attribute`` lazily compiles an every-node-flagged variant of
        # the same program and re-runs the same inputs once (the program
        # is deterministic, injected faults included) so the error still
        # names the first producing node in plan postorder.
        # ``check_numerics="all"`` puts per-node flags in the primary
        # program instead.  Flag labels are recorded at trace time
        # (re-recorded on retrace), one list per variant.

        def make_fn(ectx, laid=()):
            # laid: (stationary input, its layouts) pairs whose operands
            # the program takes, laid out, after its inputs
            labels: list = []

            def fn(*arrays):
                if ectx is not None:
                    ectx.flags.clear()   # stale flags from aborted traces
                env = {n: TensorRelation(a, rtypes[n])
                       for n, a in zip(names, arrays)}
                ops = iter(arrays[len(names):])
                for n, layouts in laid:
                    env[n] = Prelaid(env[n].data, rtypes[n], operands={
                        lay: next(ops) for lay in layouts})
                outs = eval_all(env, ectx)
                datas = tuple(r.data for r in outs)
                if ectx is not None and ectx.check:
                    pairs = ectx.take_flags()
                elif check:
                    from repro.core.guards import finite_flag
                    pairs = [(f"output[{i}]", finite_flag(r.data, r.mask))
                             for i, r in enumerate(outs)]
                    pairs = [(la, fl) for la, fl in pairs if fl is not None]
                else:
                    pairs = []
                labels[:] = [la for la, _ in pairs]
                if not pairs:
                    return datas
                flags = tuple(fl for _, fl in pairs)
                combined = flags[0]
                for fl in flags[1:]:
                    combined = jnp.logical_and(combined, fl)
                return datas + (combined,) + flags

            return fn, labels

        fn, flag_labels = make_fn(ctx)
        jfn = jax.jit(fn)
        nout = len(out_infos)
        attrib: dict = {}

        def attribute(args):
            """Re-run with every node flagged; raise naming the first."""
            from repro.core.guards import ExecContext, NumericsError, \
                label_nodes
            if "jfn" not in attrib:
                ctx2 = ExecContext(faults=self.fault_injector, check="all",
                                   stream=stream, labels=label_nodes(plans))
                fn2, labels2 = make_fn(ctx2)
                attrib["jfn"], attrib["labels"] = jax.jit(fn2), labels2
            res = attrib["jfn"](*args)
            flags = res[nout:]
            if flags and not bool(flags[0]):
                for lab, fl in zip(attrib["labels"], flags[1:]):
                    if not bool(fl):
                        raise NumericsError(
                            f"non-finite values first produced by node "
                            f"{lab} (jit finite-flags; plan postorder "
                            f"attribution)", node_label=lab)

        def finish(res, args, labels):
            datas, flags = res[:nout], res[nout:]
            if flags and not bool(flags[0]):
                from repro.core.guards import NumericsError
                if check != "all":
                    attribute(args)   # raises when it reproduces
                for lab, fl in zip(labels, flags[1:]):
                    if not bool(fl):
                        raise NumericsError(
                            f"non-finite values first produced by node "
                            f"{lab} (jit finite-flags; plan postorder "
                            f"attribution)", node_label=lab)
                raise NumericsError(
                    "non-finite values in jit outputs (attribution "
                    "re-run did not reproduce the failure)")
            return tuple(TensorRelation(d, oi.rtype, oi.mask)
                         for d, oi in zip(datas, out_infos))

        def call(env):
            args = tuple(env[n].data for n in names)
            return finish(jfn(*args), args, flag_labels)

        def layouts_of(stationary, args):
            """(input, layouts) of the stationary inputs' contraction
            operands that the program would lay out itself, found by an
            abstract trace."""
            seen: Dict[str, Prelaid] = {}

            def probe(*arrays):
                env = {n: (Prelaid if n in stationary else TensorRelation)(
                    a, rtypes[n]) for n, a in zip(names, arrays)}
                seen.update((n, env[n]) for n in stationary)
                return tuple(r.data for r in eval_all(env, None))

            jax.eval_shape(probe, *args)
            return tuple((n, tuple(seen[n].missing)) for n in sorted(seen)
                         if seen[n].missing)

        variants: dict = {}   # stationary names -> (laid, program, labels)

        def prelaid_call(env, stationary):
            args = tuple(env[n].data for n in names)
            if stationary not in variants:
                laid = layouts_of(stationary, args)
                fn2, labels2 = make_fn(None, laid)
                variants[stationary] = (laid, jax.jit(fn2), labels2)
            laid, jfn2, labels2 = variants[stationary]
            if not laid:
                return call(env)
            ops = tuple(self.prelayouts.operand(n, env[n].data, lay)
                        for n, layouts in laid for lay in layouts)
            return finish(jfn2(*args, *ops), args, labels2)

        # a robustness context (faults, per-node checks, streaming) runs
        # every dispatch through the one program it was built into
        return CompiledExpr("jit", plans, rtypes, out_infos, call, opts,
                            multi, jitted=jfn, input_names=tuple(names),
                            prelaid_call=prelaid_call if ctx is None
                            else None)

    def _gspmd_call(self, plans, out_infos, chunk, ctx=None):
        jfn, names, shardings = _jit_ia_plans(
            plans, self.mesh, chunk=chunk, budget=self.memory_budget,
            ctx=ctx)
        # jit refuses a committed array laid out other than its input
        # sharding, such as a train step's output fed back as an input
        place = _placer(shardings)

        def call(env):
            datas = jfn(*place(tuple(env[n].data for n in names)))
            return tuple(TensorRelation(d, oi.rtype, oi.mask)
                         for d, oi in zip(datas, out_infos))

        return call, jfn, tuple(names)

    def _shardmap_call(self, plans, chunk, ctx=None):
        from repro.core.shardmap_exec import _build_shardmap
        call, _, _ = _build_shardmap(plans, self.mesh, chunk=chunk,
                                     budget=self.memory_budget, ctx=ctx)
        return call
