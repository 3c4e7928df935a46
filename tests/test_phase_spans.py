"""The program's phase spans: the bounded recorder in
``repro.launch.metering`` and the spans the engine, the server and the
trainer write to the process's recorder, ``metering.SPANS``."""
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Engine, RelType, SGD, TensorRelation, TraTrainer
from repro.core.kernels_registry import get_kernel
from repro.core.programs import ffnn_train_step_tra
from repro.core.tra import fused_join_agg
from repro.kernels.matmul.ops import LAYOUT_SCOPE, matmul
from repro.launch import metering
from repro.launch.metering import SpanRecorder
from repro.serve import FFNNScorer, TraServer


# -- the recorder --------------------------------------------------------

def test_record_keeps_prestamped_intervals_in_order():
    rec = SpanRecorder(capacity=8)
    rec.record("server.queue", 1.0, 1.5)
    rec.record("server.tick", 2.0, 2.25)
    assert rec.spans() == [("server.queue", 1.0, 1.5),
                           ("server.tick", 2.0, 2.25)]
    assert rec.written == 2 and rec.dropped == 0


def test_ring_wraps_to_the_newest_and_counts_what_it_dropped():
    rec = SpanRecorder(capacity=4)
    for k in range(10):
        rec.record("a", float(k), k + 0.5)
    assert [s[1] for s in rec.spans()] == [6.0, 7.0, 8.0, 9.0]
    assert rec.written == 10 and rec.dropped == 6


def test_totals_count_every_span_per_name_dropped_ones_included():
    rec = SpanRecorder(capacity=2)
    for k in range(5):
        rec.record("a", 0.0, 0.25)
    rec.record("b", 1.0, 4.0)
    assert rec.totals() == {"a": (5, 1.25), "b": (1, 3.0)}
    assert [s[0] for s in rec.spans()] == ["a", "b"]


def test_span_records_its_interval_and_records_on_error():
    rec = SpanRecorder()
    with rec.span("engine.lookup"):
        pass
    with pytest.raises(ValueError):
        with rec.span("engine.plan"):
            raise ValueError("boom")
    (n0, a0, b0), (n1, a1, b1) = rec.spans()
    assert (n0, n1) == ("engine.lookup", "engine.plan")
    assert a0 <= b0 <= a1 <= b1


def test_recorder_rejects_an_empty_ring():
    with pytest.raises(ValueError):
        SpanRecorder(capacity=0)


# -- what the program records ----------------------------------------------

def _new_spans(written_before):
    """The spans the process recorded since ``written_before``."""
    held = metering.SPANS.spans()
    return held[len(held) - (metering.SPANS.written - written_before):]


def _names(spans):
    return Counter(name for name, _, _ in spans)


def _scorer_server():
    scorer = FFNNScorer(db=2, hb=2, lb=1, bd=4, bh=8, bl=2, buckets=(1, 2, 4))
    server = TraServer(Engine(executor="jit"), scorer)
    server.warmup()
    return scorer, server


def test_server_tick_records_its_phases_and_each_requests_queue_wait():
    scorer, server = _scorer_server()
    rng = np.random.default_rng(0)
    server.serve([scorer.random_payload(rng) for _ in range(3)])  # first run
    handles = [server.submit(scorer.random_payload(rng)) for _ in range(3)]
    before = metering.SPANS.written
    server.step()
    spans = _new_spans(before)
    assert all(h.done() for h in handles)
    assert _names(spans) == Counter({
        "server.tick": 1, "server.sweep": 1, "engine.lookup": 1,
        "server.pack": 1, "engine.dispatch": 1, "server.unpack": 1,
        "server.queue": 3})
    tick = next(s for s in spans if s[0] == "server.tick")
    for name, a, b in spans:
        if name != "server.queue":
            assert tick[1] <= a <= b <= tick[2], name
    queue = sorted((a, b) for name, a, b in spans if name == "server.queue")
    assert queue == sorted((h.span.t_submit, h.span.t_start)
                           for h in handles)


def test_first_run_and_plan_are_recorded_once_per_artifact():
    scorer, server = _scorer_server()
    rng = np.random.default_rng(1)
    before = metering.SPANS.written
    for n in (1, 1, 2, 2):
        server.serve([scorer.random_payload(rng) for _ in range(n)])
    names = _names(_new_spans(before))
    assert names["engine.plan"] == 0            # warm-up compiled them
    assert names["engine.first_run"] == 2       # buckets 1 and 2
    assert names["engine.dispatch"] == 2
    before = metering.SPANS.written
    engine = Engine(executor="jit")
    compiled = engine.compile(scorer.program(4))
    engine.compile(scorer.program(4))
    names = _names(_new_spans(before))
    assert names["engine.plan"] == 1 and names["engine.lookup"] == 2
    assert not compiled.ran


def test_train_step_records_lookup_and_dispatch_inside_the_step():
    dims = (4, 2, 2, 2, 4, 4, 4, 2)
    nb, db, hb, lb, bn, bd, bh, bl = dims
    keys = jax.random.split(jax.random.PRNGKey(0), 4)

    def rel(k, kshape, block):
        shape = kshape + block
        return TensorRelation(jax.random.normal(k, shape) * 0.1,
                              RelType(kshape, block))

    params = {"W1": rel(keys[0], (db, hb), (bd, bh)),
              "W2": rel(keys[1], (hb, lb), (bh, bl))}
    data = {"X": rel(keys[2], (nb, db), (bn, bd)),
            "Y": TensorRelation(
                (jax.random.uniform(keys[3], (nb, lb, bn, bl)) > 0.5
                 ).astype(jnp.float32), RelType((nb, lb), (bn, bl)))}
    trainer = TraTrainer(Engine(executor="jit"),
                         ffnn_train_step_tra(*dims, optimizer=SGD(0.1)),
                         params=params)
    before = metering.SPANS.written
    trainer.step(**data)
    trainer.step(**data)
    spans = _new_spans(before)
    assert _names(spans) == Counter({
        "train.step": 2, "engine.lookup": 2, "engine.plan": 1,
        "engine.first_run": 1, "engine.dispatch": 1})
    steps = [s for s in spans if s[0] == "train.step"]
    dispatch = next(s for s in spans if s[0] == "engine.dispatch")
    assert steps[1][1] <= dispatch[1] <= dispatch[2] <= steps[1][2]


# -- the layout copies' named scope ---------------------------------------

def test_fused_contraction_relayouts_carry_the_layout_scope():
    """Σ∘⋈ over (3, 4) × (4, 3) blocks of 4 × 4: both operands are
    transposed to 2-D, and their device ops are named under the scope."""
    mm, add = get_kernel("matMul"), get_kernel("matAdd")

    def contract(a, b):
        left = TensorRelation(a, RelType((3, 4), (4, 4)))
        right = TensorRelation(b, RelType((4, 3), (4, 4)))
        return fused_join_agg(left, right, (1,), (0,), mm, (0, 2), add).data

    a = jnp.arange(3 * 4 * 16, dtype=jnp.float32).reshape(3, 4, 4, 4)
    b = jnp.ones((4, 3, 4, 4), jnp.float32)
    hlo = jax.jit(contract).lower(a, b).compile().as_text()
    assert LAYOUT_SCOPE + "/transpose" in hlo
    want = jnp.einsum("ikmc,kjcn->ijmn", a, b)
    np.testing.assert_allclose(np.asarray(jax.jit(contract)(a, b)),
                               np.asarray(want), rtol=1e-5)


def test_kernel_block_padding_carries_the_layout_scope():
    a = jnp.ones((10, 24), jnp.float32)
    b = jnp.ones((24, 12), jnp.float32)
    fn = jax.jit(lambda a, b: matmul(a, b, impl="pallas", block_m=8,
                                     block_n=8, block_k=16, interpret=True))
    hlo = fn.lower(a, b).compile().as_text()
    scoped = set(re.findall(r'op_name="[^"]*' + LAYOUT_SCOPE
                            + r'/([^"]*)"', hlo))
    assert any(op.endswith("pad") for op in scoped), scoped
    assert "slice" in scoped, scoped
    np.testing.assert_allclose(np.asarray(fn(a, b)), 24.0)
