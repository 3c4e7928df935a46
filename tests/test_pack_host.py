"""Host-side packing of a scoring batch: one transfer each way.

A batch of ``n`` host payloads is laid out in one zero-padded host
buffer and crosses to the device in one ``jax.device_put``; its scores
come back in one ``jax.device_get``.  The bytes the program sees, and
the answers, are bit-equal to stacking the rows on the device.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Engine
from repro.core.engine import CompiledExpr
from repro.core.tra import RelType, TensorRelation, pack_rows, unpack_rows
from repro.serve import FFNNScorer, TraServer

BUCKETS = (1, 2, 4, 8)
BATCHES = [(b, n) for b in BUCKETS for n in range(1, b + 1)]


def _scorer():
    return FFNNScorer(db=4, hb=3, lb=2, bd=4, bh=8, bl=2, buckets=BUCKETS)


def _payloads(sc, n, seed=0):
    rng = np.random.default_rng(seed)
    return [sc.random_payload(rng) for _ in range(n)]


def _device_rows(sc, payloads):
    """The payloads as relations already on the device, one per row."""
    return [TensorRelation(jnp.asarray(p, jnp.float32)
                           .reshape(sc.db, 1, sc.bd), sc._row_rtype)
            for p in payloads]


@pytest.fixture
def transfers(monkeypatch):
    """Counts of explicit host-to-device and device-to-host transfers."""
    counts = {"put": 0, "get": 0}
    put, get = jax.device_put, jax.device_get

    def counted_put(*args, **kwargs):
        counts["put"] += 1
        return put(*args, **kwargs)

    def counted_get(*args, **kwargs):
        counts["get"] += 1
        return get(*args, **kwargs)

    monkeypatch.setattr(jax, "device_put", counted_put)
    monkeypatch.setattr(jax, "device_get", counted_get)
    return counts


@pytest.mark.parametrize("bucket,n", BATCHES)
def test_host_pack_is_bit_equal_to_the_device_path(bucket, n):
    sc = _scorer()
    payloads = _payloads(sc, n, seed=bucket * 10 + n)
    got = sc.pack(payloads, bucket)["X"]
    want = pack_rows(_device_rows(sc, payloads), bucket, sc._row_rtype)
    assert got.rtype == want.rtype
    assert got.data.dtype == want.data.dtype == jnp.float32
    host = np.asarray(got.data)
    assert host.shape == (bucket, sc.db, 1, sc.bd)
    assert np.array_equal(host, np.asarray(want.data))
    assert not host[n:].any()
    for i, p in enumerate(payloads):
        assert np.array_equal(host[i].reshape(-1), p)


@pytest.mark.parametrize("bucket", BUCKETS)
def test_unpack_is_bit_equal_to_the_per_row_path(bucket):
    sc = _scorer()
    engine = Engine(executor="jit")
    compiled = engine.compile(sc.program(bucket))
    for n in range(1, bucket + 1):
        payloads = _payloads(sc, n, seed=n)
        outs = compiled.run(**sc.pack(payloads, bucket),
                            stationary=sc.weights())
        got = sc.unpack(outs, n)
        want = [np.asarray(r.data).reshape(sc.d_out)
                for r in unpack_rows(outs["scores"], n)]
        assert len(got) == n
        for g, w in zip(got, want):
            assert isinstance(g, np.ndarray)
            assert g.shape == (sc.d_out,) and g.dtype == np.float32
            assert np.array_equal(g, w)


@pytest.mark.parametrize("bucket,n", BATCHES)
def test_a_batch_costs_one_transfer_each_way(bucket, n, transfers):
    sc = _scorer()
    compiled = Engine(executor="jit").compile(sc.program(bucket))
    payloads = _payloads(sc, n, seed=n)
    # an implicit transfer (a device slice, zeros made on the device)
    # raises under the guard; the explicit put and get are allowed
    with jax.transfer_guard("disallow"):
        packed = sc.pack(payloads, bucket)
    assert transfers == {"put": 1, "get": 0}
    outs = compiled.run(**packed, stationary=sc.weights())
    with jax.transfer_guard("disallow"):
        results = sc.unpack(outs, n)
    assert transfers == {"put": 1, "get": 1}
    for got, p in zip(results, payloads):
        np.testing.assert_allclose(got, sc.oracle(p), atol=1e-5)


def test_wrong_payload_shape_raises_before_any_transfer(transfers):
    sc = _scorer()
    good = _payloads(sc, 2)
    with pytest.raises(ValueError):
        sc.pack([good[0], np.zeros(sc.d_in + 1, np.float32), good[1]], 4)
    with pytest.raises(ValueError):
        pack_rows([np.zeros((sc.db, 1, sc.bd + 1), np.float32)], 2,
                  sc._row_rtype)
    assert transfers == {"put": 0, "get": 0}


def test_wrong_payload_shape_fails_the_batch_before_dispatch(monkeypatch):
    sc = _scorer()
    server = TraServer(Engine(executor="jit"), sc)
    server.warmup()
    runs = []
    run = CompiledExpr.run

    def counted_run(self, *args, **kwargs):
        runs.append(self)
        return run(self, *args, **kwargs)

    monkeypatch.setattr(CompiledExpr, "run", counted_run)
    bad = server.submit(np.zeros(sc.d_in - 1, np.float32))
    server.step()
    with pytest.raises(ValueError):
        bad.result(timeout=0)
    assert runs == []
    good = _payloads(sc, 3, seed=4)
    for got, p in zip(server.serve(good), good):
        np.testing.assert_allclose(got, sc.oracle(p), atol=1e-5)
    assert len(runs) == 1


@pytest.mark.parametrize("kind", ["relation", "device_array"])
def test_rows_on_the_device_keep_the_device_path(kind, transfers):
    sc = _scorer()
    payloads = _payloads(sc, 3, seed=9)
    rows = _device_rows(sc, payloads)
    if kind == "device_array":
        rows = [r.data for r in rows]
    packed = pack_rows(rows, 4, sc._row_rtype)
    assert transfers == {"put": 0, "get": 0}
    assert packed.rtype.key_shape == (4, sc.db)
    host = np.asarray(packed.data)
    assert np.array_equal(host, np.asarray(sc.pack(payloads, 4)["X"].data))
    assert not host[3:].any()


def test_host_rows_of_any_dtype_are_cast_to_the_row_type():
    rtype = RelType((2,), (1, 3))
    rows = [np.arange(6, dtype=np.float64).reshape(2, 1, 3),
            [[[1, 2, 3]], [[4, 5, 6]]]]
    packed = pack_rows(rows, 2, rtype)
    want = pack_rows([jnp.asarray(r, jnp.float32) for r in rows], 2, rtype)
    assert packed.data.dtype == jnp.float32
    assert np.array_equal(np.asarray(packed.data), np.asarray(want.data))
