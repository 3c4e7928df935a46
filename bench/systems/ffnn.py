"""The §5.3 FFNN driven through the program under test.

Training runs ``repro.core.programs.ffnn_train_step_tra`` through an
``Engine`` and ``TraTrainer``; scoring runs ``FFNNScorer`` behind
``TraServer``.  The weights and data are the benchmark's own
(``bench/models/ffnn.py``), made from the seed and handed over as
relations.
"""
from __future__ import annotations

import collections
import gc
import math
from typing import Dict, List

import models.ffnn as model


def _engine():
    from repro.core import Engine
    return Engine(executor="jit")


def mesh_engine(cfg, devices):
    """The engine over the configuration's ``mesh`` of the first of
    ``devices``, with its executor and the input placements that
    ``repro.core.programs.<placements>`` gives; and the sharding of each
    placed input by name, over the relation layout ``(k0, k1, b0, b1)``
    (an input placed nowhere is replicated)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import Engine, programs
    spec = cfg["mesh"]
    shape = tuple(spec["shape"])
    grid = np.array(devices[:math.prod(shape)]).reshape(shape)
    mesh = Mesh(grid, tuple(spec["axes"]))
    placements = getattr(programs, spec["placements"])(
        *model.block_dims(cfg)[:4])

    def sharding(p):
        if p.is_replicated:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(*(p.axis_of_dim(d) for d in range(2))))

    engine = Engine(mesh, executor=spec["executor"],
                    input_placements=placements)
    shardings = collections.defaultdict(lambda: NamedSharding(mesh, P()))
    shardings.update({n: sharding(p) for n, p in placements.items()})
    return engine, shardings


def _rel(array, keys, block):
    from repro.core import RelType, TensorRelation
    return TensorRelation(array, RelType(tuple(keys), tuple(block)))


class TrainSession:
    """One ``TraTrainer`` with its seeded parameters and minibatches.

    ``step(k)`` runs one optimizer step on minibatch ``k`` (of the
    traffic's ``minibatches``, in turn) and returns the loss on the host.
    ``first_steps(n)`` runs the first ``n`` and returns what the check
    compares: each step's loss, each leaf's first gradient as SGD got it,
    ``(W0 − W1)/lr``, and each leaf's change after step ``n``.

    A configuration with a ``mesh`` runs on :func:`mesh_engine` over the
    first chips; the data and weights are made there, laid out as
    placed."""

    def __init__(self, cfg, traffic, seed: int):
        from repro.core import SGD, TraTrainer
        from repro.core.programs import ffnn_train_step_tra
        self.cfg, self.seed = cfg, seed
        nb, db, hb, lb, bn, bd, bh, bl = dims = model.block_dims(cfg)
        self.minibatches = traffic["minibatches"]
        if "mesh" in cfg:
            import jax
            engine, shardings = mesh_engine(cfg, jax.devices())
            shardings = tuple(shardings[n] for n in ("W1", "W2", "X", "Y"))
        else:
            engine, shardings = _engine(), None
        w1, w2, xs, ys = model.make_train_data(cfg, seed, self.minibatches,
                                               shardings)
        xs = [_rel(x, (nb, db), (bn, bd)) for x in xs]
        self.feed: List[Dict[str, object]] = [
            {"X": xs[k % len(xs)], "Y": _rel(y, (nb, lb), (bn, bl))}
            for k, y in enumerate(ys)]
        step = ffnn_train_step_tra(*dims, optimizer=SGD(cfg["lr"]))
        self.trainer = TraTrainer(
            engine, step,
            params={"W1": _rel(w1, (db, hb), (bd, bh)),
                    "W2": _rel(w2, (hb, lb), (bh, bl))})

    def step(self, k: int) -> float:
        return self.trainer.step(**self.feed[k % self.minibatches])

    def _params(self):
        return {n: r.data for n, r in self.trainer.params.items()}

    def first_steps(self, n: int) -> Dict[str, object]:
        losses = [self.step(0)]
        moved = model.change_norms(self.cfg, self.seed, self._params(),
                                   layout="relation")
        grads = {k: v / self.cfg["lr"] for k, v in moved.items()}
        losses += [self.step(k) for k in range(1, n)]
        return {"losses": losses, "grad_norms": grads,
                "change_norms": model.change_norms(
                    self.cfg, self.seed, self._params(), layout="relation")}

    def close(self) -> None:
        del self.trainer, self.feed
        gc.collect()


class ServeSession:
    """``FFNNScorer`` at the configuration's widths behind ``TraServer``,
    with the seeded weights, its bucket programs compiled and every batch
    size the traffic can form dispatched once."""

    def __init__(self, cfg, traffic, seed: int):
        from repro.core import RelType, TensorRelation
        from repro.serve import FFNNScorer, TraServer
        _, db, hb, lb, _, bd, bh, bl = model.block_dims(cfg)
        buckets = tuple(traffic["buckets"])
        self.scorer = FFNNScorer(db, hb, lb, bd, bh, bl, buckets=buckets)
        w1, w2 = model.make_weights(cfg, seed)
        # the scorer's own random weights give way to the seeded ones
        self.scorer._weights = {
            "scorer.W1": TensorRelation(w1, RelType((db, hb), (bd, bh))),
            "scorer.W2": TensorRelation(w2, RelType((hb, lb), (bh, bl)))}
        gc.collect()
        self.server = TraServer(_engine(), self.scorer)
        self.server.warmup()
        warm = model.make_requests(cfg, seed ^ 0x5EED, max(buckets))
        for _ in range(2):
            for n in range(1, max(buckets) + 1):
                self.server.serve(list(warm[:n]))
        self.dispatched_before = dict(self.server.dispatches)

    def submit(self, payload):
        return self.server.submit(payload)

    def tick(self) -> int:
        return self.server.step()

    def rows_dispatched(self) -> int:
        """Bucket rows dispatched since warm-up, from the server's counts."""
        rows = 0
        for b in self.scorer.buckets:
            aid = self.server.engine.compile(self.scorer.program(b)).artifact_id
            rows += b * (self.server.dispatches.get(aid, 0)
                         - self.dispatched_before.get(aid, 0))
        return rows

    def counters(self) -> Dict[str, int]:
        return dict(self.server.counters)

    def close(self) -> None:
        del self.server, self.scorer
        gc.collect()

