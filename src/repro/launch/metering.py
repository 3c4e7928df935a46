"""Structural roofline metering: exact-by-construction FLOPs / HBM bytes /
collective bytes for a (config × shape × plan) cell.

Why this exists: ``compiled.cost_analysis()`` does NOT multiply while-loop
bodies by their trip counts (verified empirically: a 10-step scanned
matmul reports 1.000000× the flops of a single matmul), and our models
scan over layers / microbatches / attention blocks — so the XLA numbers
undercount by the product of loop trip counts.  The dry-run records both:
the raw XLA numbers (labeled per-iteration) and these structural numbers,
which enumerate every matmul/attention/scan in the model analytically.
The same formulas are the napkin-math engine for the §Perf hypothesis
loop.

Conventions: FLOPs count multiply+add (2·M·N·K per matmul).  Backward =
2× forward matmul flops; the "full" remat policy recomputes the forward
(+1×); "dots_saveable" recomputes only cheap elementwise ops (+~5%).
HBM bytes: every weight is read once per microbatch per pass (fwd, bwd-
dX, bwd-dW → 3×); activations are written+read once per layer boundary;
optimizer reads+writes master/m/v.  Collective bytes follow the TRA
plan: ring-collective wire volume  ≈ payload × (axis−1)/axis per hop
direction (reduce-scatter and all-gather each move ≈ payload; all-reduce
= RS + AG = 2× payload).
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from collections import deque
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig, ShapeSpec
from repro.launch.analysis import HBM_BW, ICI_BW, PEAK_FLOPS

if TYPE_CHECKING:
    from repro.sharding.planner import ArchPlan


@dataclasses.dataclass
class Meter:
    flops: float = 0.0          # global FLOPs per step
    hbm_bytes: float = 0.0      # global HBM traffic per step
    coll_bytes: float = 0.0     # global wire bytes per step
    detail: Dict[str, float] = dataclasses.field(default_factory=dict)

    def add(self, key: str, *, flops: float = 0.0, hbm: float = 0.0,
            coll: float = 0.0) -> None:
        self.flops += flops
        self.hbm_bytes += hbm
        self.coll_bytes += coll
        if flops:
            self.detail[f"flops/{key}"] = \
                self.detail.get(f"flops/{key}", 0.0) + flops
        if coll:
            self.detail[f"coll/{key}"] = \
                self.detail.get(f"coll/{key}", 0.0) + coll


def _ring(payload_bytes: float, axis: int) -> float:
    """Wire bytes of one reduce-scatter or all-gather over ``axis``."""
    if axis <= 1:
        return 0.0
    return payload_bytes * (axis - 1)


BP = 2  # bf16 weight/activation bytes


def _layer_weight_bytes(cfg: ModelConfig) -> Dict[str, float]:
    d = cfg.d_model
    out = {}
    if cfg.has_attention:
        if cfg.use_mla:
            w = d * cfg.q_dim + d * (cfg.kv_lora_rank + cfg.qk_rope_dim) \
                + cfg.kv_lora_rank * cfg.n_heads * (cfg.qk_nope_dim
                                                    + cfg.v_head_dim) \
                + cfg.n_heads * cfg.v_head_dim * d
        else:
            hd = cfg.head_dim
            w = d * cfg.n_heads * hd * 2 + d * cfg.n_kv_heads * hd * 2
        out["attn"] = w * BP
    if cfg.d_ff and cfg.family != "moe":
        out["mlp"] = 3 * d * cfg.d_ff * BP
    if cfg.n_experts:
        out["moe"] = (3 * cfg.n_experts * d * cfg.d_ff_expert
                      + 3 * cfg.n_shared_experts * d * cfg.d_ff_expert
                      + d * cfg.n_experts) * BP
    if cfg.ssm_state:
        di = cfg.d_inner
        out["ssm"] = (d * (2 * di + 2 * cfg.ssm_ngroups * cfg.ssm_state
                           + cfg.ssm_heads) + di * d) * BP
    return out


def _attn_flops(cfg: ModelConfig, t: int, kv_len: int, window: int,
                causal_square: bool = True) -> float:
    """Projections + scores + PV for t query tokens against kv_len keys."""
    d = cfg.d_model
    if cfg.use_mla:
        qd, r = cfg.q_dim, cfg.kv_lora_rank
        proj = 2 * t * d * qd + 2 * t * d * (r + cfg.qk_rope_dim) \
            + 2 * t * r * cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim) \
            + 2 * t * cfg.n_heads * cfg.v_head_dim * d
        per_head = cfg.qk_nope_dim + cfg.qk_rope_dim + cfg.v_head_dim
        attn = 2 * t * kv_len * cfg.n_heads * per_head
    else:
        hd = cfg.head_dim
        proj = 2 * t * d * (cfg.n_heads * hd * 2
                            + cfg.n_kv_heads * hd * 2)
        attn = 2 * t * kv_len * cfg.n_heads * hd * 2
    if window and window < kv_len:
        attn *= window / kv_len
    elif causal_square:
        attn *= 0.5          # causal: half the square
    return proj + attn


def _mamba_flops(cfg: ModelConfig, t: int) -> float:
    d, di = cfg.d_model, cfg.d_inner
    g, n, h, p = (cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_heads,
                  cfg.ssm_head_dim)
    proj = 2 * t * d * (2 * di + 2 * g * n + h) + 2 * t * di * d
    conv = 2 * t * (di + 2 * g * n) * cfg.ssm_conv_width
    L = min(cfg.ssm_chunk, t)
    # intra-chunk: scores 2L²n + masked-mix 2L²hp ; states/inter: ≈4Lnhp
    per_chunk = 2 * L * L * n + 2 * L * L * h * p + 4 * L * n * h * p
    ssd = per_chunk * max(t // L, 1)
    return proj + conv + ssd


def _mlp_flops(d: int, ff: int, t: int) -> float:
    return 3 * 2 * t * d * ff


def _moe_flops(cfg: ModelConfig, t: int) -> float:
    routed = t * cfg.top_k * cfg.moe_capacity_factor
    f = _mlp_flops(cfg.d_model, cfg.d_ff_expert, int(routed))
    f += 2 * t * cfg.d_model * cfg.n_experts          # router
    if cfg.n_shared_experts:
        f += _mlp_flops(cfg.d_model,
                        cfg.d_ff_expert * cfg.n_shared_experts, t)
    return f


def _strategy(plan: ArchPlan, comp: str) -> str:
    # imported here: the planner imports repro.core, which records its
    # phase spans through this module
    from repro.sharding.planner import PairDecision
    dec = plan.decisions.get(comp)
    if isinstance(dec, PairDecision):
        return dec.strategy
    if isinstance(dec, str) and dec.startswith("ep"):
        return "ep"
    if isinstance(dec, str) and dec.startswith("tp"):
        return "tp"
    return "dp"


def meter(cfg: ModelConfig, shape: ShapeSpec, plan: ArchPlan) -> Meter:
    m = Meter()
    sd, sm = plan.mesh.data_size, plan.mesh.model_size
    train = shape.kind == "train"
    decode = shape.kind == "decode"
    S = shape.seq_len
    t_tokens = shape.global_batch * (1 if decode else S)
    kv_len = S
    accum = max(1, shape.global_batch // max(sd, 1)) if train else 1
    # pass multiplier: fwd=1; train adds bwd (2×) and remat recompute
    if train:
        pass_mult = {"none": 3.0, "dots_saveable": 3.15,
                     "full": 4.0}[cfg.remat]
    else:
        pass_mult = 1.0

    d, V = cfg.d_model, cfg.vocab_size
    wbytes = _layer_weight_bytes(cfg)
    n_attn_layers = 0
    n_mamba_layers = 0
    n_moe_layers = 0
    n_mlp_layers = 0
    if cfg.family in ("dense", "audio", "vlm"):
        n_attn_layers = cfg.n_layers
        n_mlp_layers = cfg.n_layers
    elif cfg.family == "moe":
        n_attn_layers = cfg.n_layers
        n_moe_layers = cfg.n_layers - cfg.first_dense_layers
        n_mlp_layers = cfg.first_dense_layers
    elif cfg.family == "ssm":
        n_mamba_layers = cfg.n_layers
    elif cfg.family == "hybrid":
        n_mamba_layers = cfg.n_layers
        n_attn_layers = cfg.n_layers // cfg.mamba_per_group
        n_mlp_layers = n_attn_layers

    # ---------------- FLOPs ----------------
    csq = not decode
    if n_attn_layers:
        if cfg.local_global_period:
            loc = n_attn_layers // 2
            glob = n_attn_layers - loc
            f = loc * _attn_flops(cfg, t_tokens, kv_len, cfg.attn_window,
                                  csq) \
                + glob * _attn_flops(cfg, t_tokens, kv_len, 0, csq)
        else:
            f = n_attn_layers * _attn_flops(cfg, t_tokens, kv_len,
                                            cfg.attn_window, csq)
        m.add("attn", flops=f * pass_mult)
    if n_mlp_layers:
        ff = cfg.d_ff or 4 * d
        m.add("mlp", flops=n_mlp_layers * _mlp_flops(d, ff, t_tokens)
              * pass_mult)
    if n_moe_layers:
        m.add("moe", flops=n_moe_layers * _moe_flops(cfg, t_tokens)
              * pass_mult)
    if n_mamba_layers:
        if decode:
            di = cfg.d_inner
            per_tok = (2 * d * (2 * di + 2 * cfg.ssm_ngroups
                                * cfg.ssm_state + cfg.ssm_heads)
                       + 2 * di * d
                       + 4 * cfg.ssm_state * cfg.ssm_heads
                       * cfg.ssm_head_dim)
            f = n_mamba_layers * per_tok * shape.global_batch
        else:
            f = n_mamba_layers * _mamba_flops(cfg, S) * shape.global_batch
        m.add("ssm", flops=f * pass_mult)
    m.add("head", flops=2 * t_tokens * d * V * pass_mult)
    if train:
        from repro.models.model import count_params
        m.add("optimizer", flops=12.0 * count_params(cfg))

    # ---------------- HBM bytes ----------------
    layer_w = 0.0
    if n_attn_layers:
        layer_w += n_attn_layers * wbytes.get("attn", 0)
    if n_mlp_layers:
        layer_w += n_mlp_layers * wbytes.get("mlp", 0)
    if n_moe_layers:
        layer_w += n_moe_layers * wbytes.get("moe", 0)
    if n_mamba_layers:
        layer_w += n_mamba_layers * wbytes.get("ssm", 0)
    head_w = d * V * BP * (1 if cfg.tie_embeddings else 2)
    total_w = layer_w + head_w
    w_reads = (3 if train else 1) * accum
    m.add("weights", hbm=total_w * w_reads)
    n_layers_total = (n_attn_layers + n_mamba_layers + n_moe_layers
                      + n_mlp_layers)
    act_bytes = t_tokens * d * BP * n_layers_total * (4 if train else 2)
    m.add("activations", hbm=act_bytes)
    if decode:
        # the KV cache / SSM state is read every step — decode's wall
        cache_bp = 1 if "float8" in (cfg.kv_cache_dtype or "") else BP
        cache = 0.0
        if n_attn_layers and not cfg.use_mla:
            cache = (n_attn_layers * 2 * cfg.n_kv_heads * cfg.head_dim
                     * kv_len * shape.global_batch * cache_bp)
        elif cfg.use_mla:
            cache = (n_attn_layers * (cfg.kv_lora_rank + cfg.qk_rope_dim)
                     * kv_len * shape.global_batch * cache_bp)
        if n_mamba_layers:
            cache += (n_mamba_layers * cfg.ssm_heads * cfg.ssm_state
                      * cfg.ssm_head_dim * 4 * shape.global_batch)
        m.add("kv-cache", hbm=cache)
    if train:
        from repro.models.model import count_params
        m.add("optimizer", hbm=count_params(cfg) * 4 * 6)  # rd+wr m/v/w f32

    # ---------------- collective bytes ----------------
    if train and sd > 1:
        # gradient sync over the data axes (RS) + ZeRO-1 param AG
        m.add("grad-sync", coll=2 * _ring(total_w, sd))
    comp_of = {"attn": ("attn", wbytes.get("attn", 0) * n_attn_layers),
               "mlp": ("mlp", wbytes.get("mlp", 0) * n_mlp_layers),
               "ssm": ("ssm", wbytes.get("ssm", 0) * n_mamba_layers),
               "moe": ("moe", wbytes.get("moe", 0) * n_moe_layers)}
    for comp, (key, wb) in comp_of.items():
        if not wb:
            continue
        strat = _strategy(plan, comp)
        nl = {"attn": n_attn_layers, "mlp": n_mlp_layers,
              "ssm": n_mamba_layers, "moe": n_moe_layers}[key]
        if strat == "fsdp" and sm > 1:
            # weights gathered over the model axis per pass (fwd+bwd)
            passes = (2 if train else 1) * accum
            m.add(f"{key}-fsdp-gather", coll=_ring(wb, sm) * passes)
        elif strat == "tp" and sm > 1:
            # Megatron: RS+AG of the activations per layer per pass
            passes = 2 if train else 1
            payload = t_tokens * d * BP
            m.add(f"{key}-tp-rs-ag",
                  coll=2 * _ring(payload, sm) * nl * passes)
        elif strat == "ep" and sm > 1:
            routed = t_tokens * cfg.top_k * cfg.moe_capacity_factor
            payload = routed * d * BP
            passes = 2 if train else 1
            m.add("moe-ep-a2a", coll=2 * payload * passes * nl)
    if plan.act_axis_map.get("vocab") and sm > 1:
        # vocab-sharded logits: logsumexp partial + dlogits path ≈ t×d
        m.add("vocab", coll=_ring(t_tokens * d * BP,
                                  sm) * (2 if train else 1))
    return m


def roofline_terms(meter_: Meter, chips: int) -> Dict[str, float]:
    c = meter_.flops / (chips * PEAK_FLOPS)
    h = meter_.hbm_bytes / (chips * HBM_BW)
    k = meter_.coll_bytes / (chips * ICI_BW)
    dom = max((c, "compute"), (h, "memory"), (k, "collective"))[1]
    return {"compute_s": c, "memory_s": h, "collective_s": k,
            "dominant": dom, "step_s": max(c, h, k)}


# ==========================================================================
# Request-span metering (repro.serve): admission → completion wall clock
# ==========================================================================
#
# The structural meters above price a (config × shape × plan) cell; a
# serving benchmark needs the *other* kind of meter — measured per-request
# spans, split into queue wait (submit → first scheduled step) and service
# (first step → completion), so BENCH_serve.json can report latency
# percentiles instead of one whole-process wall clock that hides queueing.

@dataclasses.dataclass
class RequestSpan:
    """One request's lifecycle timestamps (``time.perf_counter`` seconds).

    ``t_submit`` is stamped at queue admission, ``t_start`` when the
    scheduler first packs the request into a batch (or allocates its
    decode slot), ``t_complete`` when the result is handed back.
    ``tokens`` counts produced output units (generated tokens for decode
    servables, scored rows for stateless ones).  ``outcome`` is the
    request's fate — ``"ok"`` or one of the resilience outcomes
    (``shed`` / ``cancelled`` / ``deadline`` / ``failed``); shed spans
    complete without ever starting, so their ``t_start`` stays ``None``.
    """

    rid: int
    kind: str = "request"
    t_submit: float = 0.0
    t_start: Optional[float] = None
    t_complete: Optional[float] = None
    tokens: int = 0
    outcome: str = "ok"

    @property
    def queue_wait_s(self) -> Optional[float]:
        if self.t_start is None:
            return None
        return self.t_start - self.t_submit

    @property
    def service_s(self) -> Optional[float]:
        if self.t_start is None or self.t_complete is None:
            return None
        return self.t_complete - self.t_start

    @property
    def total_s(self) -> Optional[float]:
        if self.t_complete is None:
            return None
        return self.t_complete - self.t_submit


def percentiles(values: Sequence[float],
                qs: Sequence[float] = (50, 95, 99)) -> Dict[str, float]:
    """``{"p50": ..., "p95": ..., "p99": ...}`` by linear interpolation."""
    out: Dict[str, float] = {}
    xs = sorted(float(v) for v in values)
    for q in qs:
        name = f"p{int(q) if float(q).is_integer() else q}"
        if not xs:
            out[name] = float("nan")
            continue
        pos = (len(xs) - 1) * (q / 100.0)
        lo, hi = int(math.floor(pos)), int(math.ceil(pos))
        out[name] = xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
    return out


@dataclasses.dataclass
class StreamStats:
    """Per-plan out-of-core streaming counters (``repro.store``).

    One instance lives on each streamed compile-cache artifact
    (``Engine.cache_info()`` surfaces it) and accumulates across ``run``
    calls of that artifact.  ``copy_s`` is total wall spent issuing
    host→device chunk transfers; ``hidden_copy_s`` is the portion issued
    while the previous chunk's compute was already dispatched — the
    double-buffered prefetches — so ``overlap_efficiency`` → (n−1)/n for
    an n-chunk stream when transfers are uniform.  ``peak_device_bytes``
    is the analytic live set (resident operands + current chunk +
    prefetched chunk + output side), the quantity the memory-budget
    planner bounds.  Spill counters are deltas of the backing
    :class:`repro.store.RelationStore`'s disk tier over this plan's runs.
    """

    mode: str = "resident"          # resident | stream-out | stream-reduce
    budget_bytes: Optional[int] = None
    runs: int = 0
    chunks: int = 0
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    copy_s: float = 0.0
    hidden_copy_s: float = 0.0
    compute_s: float = 0.0
    spill_events: int = 0
    spill_bytes: int = 0
    peak_device_bytes: int = 0

    @property
    def overlap_efficiency(self) -> float:
        """Fraction of transfer wall hidden behind in-flight compute."""
        if self.copy_s <= 0.0:
            return 1.0
        return min(1.0, self.hidden_copy_s / self.copy_s)

    def as_dict(self) -> Dict[str, object]:
        return {
            "mode": self.mode,
            "budget_bytes": self.budget_bytes,
            "runs": self.runs,
            "chunks": self.chunks,
            "h2d_bytes": self.h2d_bytes,
            "d2h_bytes": self.d2h_bytes,
            "copy_s": round(self.copy_s, 6),
            "hidden_copy_s": round(self.hidden_copy_s, 6),
            "compute_s": round(self.compute_s, 6),
            "overlap_efficiency": round(self.overlap_efficiency, 4),
            "spill_events": self.spill_events,
            "spill_bytes": self.spill_bytes,
            "peak_device_bytes": self.peak_device_bytes,
        }


class SpanMeter:
    """Collects :class:`RequestSpan`\\ s and summarizes them.

    The serving layer owns exactly one meter per server; spans are opened
    at ``submit`` time and closed by the scheduler, so queue wait and
    compute are metered per request instead of folded into one
    whole-process wall clock.
    """

    def __init__(self, clock=None) -> None:
        import time
        self._clock = clock or time.perf_counter
        self.spans: list = []
        self._next_rid = 0

    def now(self) -> float:
        return self._clock()

    def open(self, kind: str = "request") -> RequestSpan:
        span = RequestSpan(rid=self._next_rid, kind=kind,
                           t_submit=self.now())
        self._next_rid += 1
        self.spans.append(span)
        return span

    def start(self, span: RequestSpan) -> None:
        if span.t_start is None:
            span.t_start = self.now()

    def complete(self, span: RequestSpan, tokens: int = 0) -> None:
        span.t_complete = self.now()
        span.tokens += tokens

    # -- reporting --------------------------------------------------------
    def completed(self) -> list:
        return [s for s in self.spans if s.t_complete is not None]

    def summary(self) -> Dict[str, object]:
        """Percentile latencies (ms) + aggregate throughput (tokens/s).

        Latency percentiles cover the spans that were actually
        *scheduled* (``t_start`` set) — shed requests fail before ever
        starting, so folding them in would deflate queue-wait and
        service numbers; they are tallied in ``outcomes`` instead.
        """
        done = self.completed()
        if not done:
            return {"requests": 0}
        served = [s for s in done if s.t_start is not None]
        outcomes: Dict[str, int] = {}
        for s in done:
            outcomes[s.outcome] = outcomes.get(s.outcome, 0) + 1
        t0 = min(s.t_submit for s in done)
        t1 = max(s.t_complete for s in done)
        window = max(t1 - t0, 1e-9)
        tokens = sum(s.tokens for s in done)
        ms = 1e3
        return {
            "requests": len(done),
            "tokens": tokens,
            "window_s": round(window, 6),
            "tokens_per_s": round(tokens / window, 3),
            "outcomes": outcomes,
            "total_ms": {k: round(v * ms, 3) for k, v in percentiles(
                [s.total_s for s in served]).items()},
            "queue_wait_ms": {k: round(v * ms, 3) for k, v in percentiles(
                [s.queue_wait_s for s in served]).items()},
            "service_ms": {k: round(v * ms, 3) for k, v in percentiles(
                [s.service_s for s in served]).items()},
        }


# ==========================================================================
# Phase spans: where the host's time goes inside a tick or a step
# ==========================================================================

class SpanRecorder:
    """A bounded, always-on record of the program's phase spans.

    A span is ``(name, start, end)``, its ends in seconds on
    ``time.perf_counter``, the clock :class:`SpanMeter` stamps requests
    with.  Spans go into a ring of ``capacity`` entries (a
    ``deque(maxlen=capacity)`` of tuples of the name and two floats,
    which the garbage collector stops tracking), so recording one costs
    the same however many came before.  Once the ring is full each span
    pushes out the oldest, which :attr:`dropped` counts: a reader can tell
    whether the ring still holds the interval it asks about.  Per name, a
    count and a total of seconds run over the whole process, dropped
    spans included.
    """

    def __init__(self, capacity: int = 1 << 16) -> None:
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.written = 0
        self._ring: deque = deque(maxlen=capacity)
        self._totals: Dict[str, List] = {}      # name -> [count, seconds]
        self._lock = threading.Lock()

    def span(self, name: str) -> "_Span":
        """Context manager recording ``name`` from entry to exit.  It also
        opens a ``jax.profiler.TraceAnnotation`` of the same name, so a
        profile of the process shows the phase."""
        return _Span(self, name)

    def record(self, name: str, start: float, end: float) -> None:
        """Store an interval whose ends were stamped already."""
        with self._lock:
            self._ring.append((name, start, end))
            self.written += 1
            total = self._totals.get(name)
            if total is None:
                self._totals[name] = [1, end - start]
            else:
                total[0] += 1
                total[1] += end - start

    @property
    def dropped(self) -> int:
        """Spans pushed out of the ring since the process started."""
        return max(0, self.written - self.capacity)

    def spans(self) -> List[Tuple[str, float, float]]:
        """The spans the ring holds, in the order they ended."""
        with self._lock:
            return list(self._ring)

    def totals(self) -> Dict[str, Tuple[int, float]]:
        """Per name: (spans recorded, their total seconds)."""
        with self._lock:
            return {name: (n, s) for name, (n, s) in self._totals.items()}


class _Span:
    __slots__ = ("_recorder", "_name", "_annotation", "_start")

    def __init__(self, recorder: SpanRecorder, name: str) -> None:
        self._recorder = recorder
        self._name = name

    def __enter__(self) -> "_Span":
        self._annotation = TraceAnnotation(self._name)
        self._annotation.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._recorder.record(self._name, self._start, end)


# The process's one recorder: the engine, the server and the trainer
# record their phases here; the benchmark reads it after a window.
SPANS = SpanRecorder()
span = SPANS.span
record = SPANS.record
