"""Stationary inputs: kernel operands laid out once per weight.

A caller passes its weights as ``CompiledExpr.run(stationary=...)``; on the
``jit`` executor the engine lays out each weight's operand of the fused
contraction's Pallas kernel (transpose, 2-D reshape, the kernel's pad)
once, keeps it in ``Engine.prelayouts`` and hands it to every artifact that
needs it.  Here the kernel runs in interpret mode, padding as on the chip.
The scorer fuses both contractions (``db`` and ``hb`` of at least 3) and
both weights move under their layouts (``lb`` of 2), so each has a copy.
"""
import functools
import gc
import weakref
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.matmul.ops as ops
from repro.core import SGD, Engine, RelType, TensorRelation, TraTrainer
from repro.core.programs import ffnn_train_step_tra
from repro.launch import metering
from repro.serve import FFNNScorer, LmRequest, RecurrentLM, TraServer

BUCKETS = (1, 2, 4, 8)


def _scorer(bh=8):
    return FFNNScorer(db=4, hb=3, lb=2, bd=4, bh=bh, bl=2, buckets=BUCKETS)


def _payloads(sc, n, seed=0):
    rng = np.random.default_rng(seed)
    return [sc.random_payload(rng) for _ in range(n)]


def _new_span_names(written_before):
    held = metering.SPANS.spans()
    return Counter(name for name, _, _ in
                   held[len(held) - (metering.SPANS.written
                                     - written_before):])


def _use_kernel(monkeypatch):
    """The engine's 2-D contraction on the Pallas kernel, interpreted."""
    kernel = ops.matmul_pallas
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "matmul_pallas", lambda *a, **kw: kernel(
        *a, **{**kw, "interpret": True}))


@pytest.fixture
def on_kernel(monkeypatch):
    _use_kernel(monkeypatch)
    ops.matmul.clear_cache()
    yield
    ops.matmul.clear_cache()


@pytest.fixture(params=["jnp", "pallas"])
def kernel_path(request, monkeypatch):
    """XLA's dot, which takes the operand from the program, or the
    kernel."""
    if request.param == "pallas":
        _use_kernel(monkeypatch)
    ops.matmul.clear_cache()
    yield request.param
    ops.matmul.clear_cache()


def test_scores_equal_with_and_without_stationary_weights(kernel_path):
    # bh = 200: the hidden width 600 pads to 1024 on the kernel's path
    sc = _scorer(bh=200)
    plain, laid = Engine(executor="jit"), Engine(executor="jit")
    payloads = _payloads(sc, max(BUCKETS))
    for b in BUCKETS:
        packed = sc.pack(payloads[:b], b)
        want = plain.compile(sc.program(b)).run(**packed, **sc.weights())
        got = laid.compile(sc.program(b)).run(**packed,
                                              stationary=sc.weights())
        assert np.array_equal(np.asarray(got["scores"].data),
                              np.asarray(want["scores"].data)), b
    assert len(plain.prelayouts) == 0
    if kernel_path == "pallas":
        # the copies are padded as the kernel pads: 16 x 1024, 1024 x 4
        assert len(laid.prelayouts) == 2
        assert laid.prelayouts.nbytes == 4 * (16 * 1024 + 1024 * 4)
    else:
        assert len(laid.prelayouts) == 0


def test_bucket_artifacts_share_one_copy_per_weight(on_kernel):
    sc = _scorer()
    server = TraServer(Engine(executor="jit"), sc)
    server.warmup()
    payloads = _payloads(sc, 8, seed=1)
    for n in (1, 2, 3, 5, 8, 1):
        results = server.serve(payloads[:n])
        for p, r in zip(payloads, results):
            np.testing.assert_allclose(r, sc.oracle(p), atol=1e-5)
    laid = server.engine.prelayouts
    assert len(laid) == 2                   # W1 and W2, not one per bucket
    assert laid.misses == 2 and laid.hits == 2 * 6 - 2
    assert laid.nbytes == sum(w.data.nbytes for w in sc.weights().values())
    assert server.cache_misses_since_warmup == 0


def test_prelayout_span_marks_each_copy_made(on_kernel):
    sc = _scorer()
    server = TraServer(Engine(executor="jit"), sc)
    server.warmup()
    payloads = _payloads(sc, 3, seed=2)
    before = metering.SPANS.written
    server.serve(payloads[:1])
    assert _new_span_names(before)["engine.prelayout"] == 2
    before = metering.SPANS.written
    server.serve(payloads[:1])
    server.serve(payloads)
    assert _new_span_names(before)["engine.prelayout"] == 0


def test_replacing_a_weight_drops_its_copy_and_serves_the_new_one(
        on_kernel):
    sc = _scorer()
    server = TraServer(Engine(executor="jit"), sc)
    server.warmup()
    payloads = _payloads(sc, 3, seed=3)
    server.serve(payloads)
    old = sc.weights()["scorer.W1"]
    gone = weakref.ref(old.data)
    sc._weights["scorer.W1"] = TensorRelation(2.0 * old.data, old.rtype)
    del old
    results = server.serve(payloads)
    for p, r in zip(payloads, results):
        np.testing.assert_allclose(r, sc.oracle(p), atol=1e-5)
    laid = server.engine.prelayouts
    assert len(laid) == 2 and laid.misses == 3
    gc.collect()
    assert gone() is None                   # the engine let go of it


def test_a_train_step_declares_nothing_stationary(on_kernel):
    dims = (4, 3, 3, 1, 4, 4, 8, 2)
    nb, db, hb, lb, bn, bd, bh, bl = dims
    keys = jax.random.split(jax.random.PRNGKey(0), 4)

    def rel(k, kshape, block):
        return TensorRelation(jax.random.normal(k, kshape + block) * 0.1,
                              RelType(kshape, block))

    engine = Engine(executor="jit")
    trainer = TraTrainer(engine, ffnn_train_step_tra(*dims,
                                                     optimizer=SGD(0.1)),
                         params={"W1": rel(keys[0], (db, hb), (bd, bh)),
                                 "W2": rel(keys[1], (hb, lb), (bh, bl))})
    data = {"X": rel(keys[2], (nb, db), (bn, bd)),
            "Y": TensorRelation(
                (jax.random.uniform(keys[3], (nb, lb, bn, bl)) > 0.5
                 ).astype(jnp.float32), RelType((nb, lb), (bn, bl)))}
    before = metering.SPANS.written
    for _ in range(2):
        trainer.step(**data)
    assert _new_span_names(before)["engine.prelayout"] == 0
    assert len(engine.prelayouts) == 0


def test_recurrent_lm_decode_matches_oracle_with_stationary_weights(
        on_kernel):
    lm = RecurrentLM(d_model=16, vocab_size=32, capacity=3, seed=1)
    server = TraServer(Engine(executor="jit"), lm, collect_logits=True)
    server.warmup()
    rng = np.random.default_rng(4)
    reqs = [LmRequest(prompt=[int(t) for t in rng.integers(0, 32, 3)],
                      max_new_tokens=4) for _ in range(5)]
    for req, res in zip(reqs, server.serve(reqs)):
        toks, logits = lm.oracle_decode(req.prompt, req.max_new_tokens)
        assert res["tokens"] == toks
        for got, want in zip(res["logits"], logits):
            np.testing.assert_allclose(got, want, atol=1e-5)


def test_reference_executor_takes_stationary_inputs_as_ordinary_ones(
        on_kernel):
    sc = _scorer()
    engine = Engine(executor="reference")
    payloads = _payloads(sc, 2, seed=5)
    out = engine.compile(sc.program(2)).run(**sc.pack(payloads, 2),
                                            stationary=sc.weights())
    for row, p in zip(np.asarray(out["scores"].data), payloads):
        np.testing.assert_allclose(row.reshape(-1), sc.oracle(p),
                                   atol=1e-5)
    assert len(engine.prelayouts) == 0


def test_prepadded_operand_is_not_padded_again():
    """K = 40 fits one block; N = 600 pads to two 512 blocks."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(6))
    a = jax.random.normal(k1, (8, 40))
    b = jax.random.normal(k2, (40, 600))
    tile = ops.tile_multiples(b.shape, "pallas")
    assert tile == (40, 512)
    bp = ops.pad_operand(b, tile)
    assert bp.shape == (40, 1024)
    prepadded = functools.partial(ops.matmul, impl="pallas", interpret=True,
                                  out_shape=(8, 600))
    jaxpr = str(jax.make_jaxpr(prepadded)(a, bp))
    assert " pad[" not in jaxpr and "pad(" not in jaxpr
    assert " pad" in str(jax.make_jaxpr(functools.partial(
        ops.matmul, impl="pallas", interpret=True))(a, b))
    got = prepadded(a, bp)
    assert got.shape == (8, 600)
    assert np.array_equal(np.asarray(got), np.asarray(
        ops.matmul(a, b, impl="pallas", interpret=True)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b),
                               rtol=1e-5, atol=1e-5)
