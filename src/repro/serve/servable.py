"""Servables: model programs a :class:`~repro.serve.server.TraServer` holds.

A *servable* is the serving-side analogue of a train-step builder
(:mod:`repro.core.train`): it owns the model weights as relations and
emits the lazy :class:`~repro.core.expr.Expr` programs the server compiles
once per shape and dispatches forever.  Two shapes of servable exist:

* :class:`BatchServable` — stateless request/response scoring.  One
  program per *bucket size*: the batched input relation gains a new
  leading **batch key dim** (``tra.pack_rows``), padded to the bucket so
  the engine's structural compile cache serves every request count from a
  small artifact set.  The §5.3 FFNN scorer (:class:`FFNNScorer`) is the
  paper-native instance.
* :class:`StepServable` — stateful step decode.  ONE program over a
  **fixed-capacity slot-keyed state relation**: the leading key dim
  indexes decode slots, admission/eviction are functional row writes
  (``tra.scatter_rows`` / ``tra.zero_rows``), and the compiled step is
  re-dispatched every engine tick with state threaded
  state-out → state-in by name, exactly like
  :class:`~repro.core.train.TraTrainer`.  :class:`RecurrentLM` is the
  smoke LM — an Elman-style recurrence sized from any model config.

Every servable also carries a **dense per-request oracle** (plain jnp,
no Engine) — the correctness reference the continuous-batching tests and
benchmarks compare against at 1e-5.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import expr as E
from repro.core.expr import Expr
from repro.core.tra import RelType, TensorRelation, to_tensor

DEFAULT_BUCKETS = (1, 2, 4, 8)


def pick_bucket(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket that fits ``n`` requests (buckets sorted asc)."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} requests exceed the largest bucket "
                     f"{max(buckets)}")


class Servable:
    """Base: a named model whose programs the server compiles and pins."""

    name: str = "servable"

    def weights(self) -> Dict[str, TensorRelation]:
        """Weight input relations fed to every dispatch (long-lived)."""
        raise NotImplementedError

    def programs(self) -> List[Dict[str, Expr]]:
        """Every program to compile at warmup (one per served shape)."""
        raise NotImplementedError


class BatchServable(Servable):
    """Stateless scoring over bucket-padded batched relations."""

    buckets: Tuple[int, ...] = DEFAULT_BUCKETS

    def program(self, bucket: int) -> Dict[str, Expr]:
        raise NotImplementedError

    def pack(self, payloads: Sequence, bucket: int
             ) -> Dict[str, TensorRelation]:
        raise NotImplementedError

    def unpack(self, outs: Dict[str, TensorRelation], n: int) -> List:
        raise NotImplementedError

    def oracle(self, payload) -> np.ndarray:
        raise NotImplementedError

    def programs(self) -> List[Dict[str, Expr]]:
        return [self.program(b) for b in self.buckets]


class StepServable(Servable):
    """Fixed-capacity slot-keyed step decode (continuous batching)."""

    capacity: int = 8

    def step_program(self) -> Dict[str, Expr]:
        """Named roots; must include ``"state"`` (threaded) and
        ``"logits"`` (per-slot outputs)."""
        raise NotImplementedError

    def init_state(self) -> TensorRelation:
        raise NotImplementedError

    def step_inputs(self, tokens: Sequence[Optional[int]]
                    ) -> Dict[str, TensorRelation]:
        """Non-state inputs for one tick; ``tokens[slot]`` is the token
        the slot consumes this tick (``None`` = free slot)."""
        raise NotImplementedError

    def next_token(self, logits_row: np.ndarray) -> int:
        raise NotImplementedError

    def oracle_decode(self, prompt: Sequence[int], max_new_tokens: int
                      ) -> Tuple[List[int], List[np.ndarray]]:
        raise NotImplementedError

    # -- fault recovery ----------------------------------------------------
    def snapshot_state(self, state: TensorRelation) -> TensorRelation:
        """Cheap host copy of the slot-keyed state — the recovery point
        the server commits after every good tick.  Pulling the buffer to
        host ``numpy`` decouples the snapshot from device lifetime (a
        faulted dispatch cannot corrupt or free it)."""
        return TensorRelation(np.array(state.data, copy=True),
                              state.rtype, state.mask)

    def restore_state(self, snapshot: TensorRelation) -> TensorRelation:
        """Re-materialize a :meth:`snapshot_state` copy on device."""
        import jax.numpy as jnp
        return TensorRelation(jnp.asarray(snapshot.data),
                              snapshot.rtype, snapshot.mask)

    def programs(self) -> List[Dict[str, Expr]]:
        return [self.step_program()]


# ==========================================================================
# §5.3 FFNN scorer — the paper's evaluation network behind a request path
# ==========================================================================

class FFNNScorer(BatchServable):
    """The §5.3 two-layer FFNN as a stateless scoring servable.

    ``scores = σ(relu(X @ W1) @ W2)`` over block-chunked relations — the
    same forward program :func:`repro.core.programs.ffnn_step_tra` trains,
    now *served*: requests are feature vectors packed into an ``X``
    relation keyed ``(bucket, db)`` with ``(1, bd)`` row blocks.  The
    batch key dim is never contracted (the contraction runs over the
    feature blocks), so every request's scores are computed independently
    of its batch neighbours — zero-padding tail rows is inert, which is
    what makes bucket padding correct.

    One program per bucket size; the weight relations are shared across
    buckets, so ``d_in = db·bd`` features in, ``d_out = lb·bl`` scores
    out, for any admitted batch.
    """

    name = "ffnn-scorer"

    def __init__(self, db: int = 2, hb: int = 2, lb: int = 1,
                 bd: int = 8, bh: int = 8, bl: int = 4,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 seed: int = 0):
        self.db, self.hb, self.lb = db, hb, lb
        self.bd, self.bh, self.bl = bd, bh, bl
        self.buckets = tuple(sorted(buckets))
        self.d_in = db * bd
        self.d_out = lb * bl
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        h = hb * bh
        w1 = jax.random.normal(k1, (db, hb, bd, bh)) * (self.d_in ** -0.5)
        w2 = jax.random.normal(k2, (hb, lb, bh, bl)) * (h ** -0.5)
        self._weights = {
            "scorer.W1": TensorRelation(w1, RelType((db, hb), (bd, bh))),
            "scorer.W2": TensorRelation(w2, RelType((hb, lb), (bh, bl))),
        }
        self._row_rtype = RelType((db,), (1, bd))
        self._programs: Dict[int, Dict[str, Expr]] = {}

    def weights(self) -> Dict[str, TensorRelation]:
        return self._weights

    def program(self, bucket: int) -> Dict[str, Expr]:
        """The bucket's scoring program (built once, cached — reusing the
        identical ``Expr`` objects keeps the engine's structural cache
        key stable across dispatches)."""
        if bucket not in self._programs:
            if bucket not in self.buckets:
                raise ValueError(
                    f"bucket {bucket} not in {self.buckets}")
            x = E.input("X", (bucket, self.db), (1, self.bd))
            w1 = E.input("scorer.W1", (self.db, self.hb),
                         (self.bd, self.bh))
            w2 = E.input("scorer.W2", (self.hb, self.lb),
                         (self.bh, self.bl))
            a2 = ((x @ w1).map("relu") @ w2).map("sigmoid")
            self._programs[bucket] = {"scores": a2}
        return self._programs[bucket]

    # -- request packing ---------------------------------------------------
    def pack(self, payloads: Sequence, bucket: int
             ) -> Dict[str, TensorRelation]:
        """One host-to-device transfer for the batch: the payloads are
        checked and laid out on the host (``tra.pack_rows``)."""
        from repro.core.tra import pack_rows
        rows = []
        for p in payloads:
            arr = np.asarray(p, np.float32)
            if arr.shape != (self.d_in,):
                raise ValueError(
                    f"scorer request must be a ({self.d_in},) feature "
                    f"vector, got {arr.shape}")
            rows.append(arr.reshape(self.db, 1, self.bd))
        return {"X": pack_rows(rows, bucket, self._row_rtype)}

    def unpack(self, outs: Dict[str, TensorRelation], n: int) -> List:
        """One device-to-host copy of the batch's scores, sliced into
        the first ``n`` rows on the host."""
        scores = jax.device_get(outs["scores"].data)
        return [scores[i].reshape(self.d_out) for i in range(n)]

    def random_payload(self, rng: np.random.Generator) -> np.ndarray:
        return rng.standard_normal(self.d_in).astype(np.float32)

    # -- dense oracle ------------------------------------------------------
    def oracle(self, payload) -> np.ndarray:
        """Per-request dense forward (plain jnp, no Engine, no batching)."""
        w1 = to_tensor(self._weights["scorer.W1"])
        w2 = to_tensor(self._weights["scorer.W2"])
        x = jnp.asarray(payload, jnp.float32)
        out = jax.nn.sigmoid(jax.nn.relu(x @ w1) @ w2)
        return np.asarray(out)


# ==========================================================================
# Smoke LM — an Elman recurrence sized from a model config
# ==========================================================================

@dataclasses.dataclass
class LmRequest:
    """A decode request: prompt token ids + generation budget."""

    prompt: List[int]
    max_new_tokens: int

    def __post_init__(self):
        if not self.prompt:
            raise ValueError("LmRequest needs a non-empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")


class RecurrentLM(StepServable):
    """Elman-style recurrent LM as ONE fixed-capacity TRA step program.

    Per slot: ``h' = relu(h @ Wh + emb(tok) @ Wx)``,
    ``logits = h' @ Wo`` — greedy sampling happens host-side (like any
    real serving loop), the recurrent state lives in the slot-keyed
    relation ``lm.state`` (key ``(capacity, 1)``, bound ``(1, d)``).  The
    step program updates state through :meth:`~repro.core.expr.Expr.
    slot_update` with the ``lm.active`` mask relation, so free /
    mid-eviction slots hold their rows bit-exactly while neighbours
    decode — the invariant behind continuous batching correctness.

    Sized from any :class:`~repro.configs.base.ModelConfig` via
    :meth:`from_config` (``d_model``/``vocab_size`` of the smoke config);
    the weights are seeded Gaussians with sub-unit spectral scale so long
    decodes stay bounded.  Not the dense transformer zoo — the point is a
    *TRA-native* stateful decode path; ``launch/serve.py --dense-oracle``
    keeps the dense-model loop for comparison.
    """

    name = "recurrent-lm"

    def __init__(self, d_model: int = 64, vocab_size: int = 256,
                 capacity: int = 8, seed: int = 0):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.d = int(d_model)
        self.vocab = int(vocab_size)
        self.capacity = int(capacity)
        d, v = self.d, self.vocab
        kh, kx, ko, ke = jax.random.split(jax.random.PRNGKey(seed), 4)
        # sub-unit recurrent gain: relu(h·Wh + e·Wx) stays bounded over
        # arbitrarily long decodes
        wh = jax.random.normal(kh, (d, d)) * (0.5 * d ** -0.5)
        wx = jax.random.normal(kx, (d, d)) * (d ** -0.5)
        wo = jax.random.normal(ko, (d, v)) * (d ** -0.5)
        self._weights = {
            "lm.Wh": TensorRelation(wh[None, None],
                                    RelType((1, 1), (d, d))),
            "lm.Wx": TensorRelation(wx[None, None],
                                    RelType((1, 1), (d, d))),
            "lm.Wo": TensorRelation(wo[None, None],
                                    RelType((1, 1), (d, v))),
        }
        # host-side table: per-tick gathers index it in numpy, so the
        # traced device shapes never depend on how many slots are live
        # (one XLA program per step, not one per live-slot count)
        self.embedding = np.asarray(
            jax.random.normal(ke, (v, d)) * (d ** -0.5), np.float32)
        self._program: Optional[Dict[str, Expr]] = None
        self._state_rtype = RelType((self.capacity, 1), (1, d))

    @classmethod
    def from_config(cls, cfg, capacity: int = 8,
                    seed: int = 0) -> "RecurrentLM":
        """Size the LM from a model config (use the smoke variant)."""
        return cls(d_model=cfg.d_model, vocab_size=cfg.vocab_size,
                   capacity=capacity, seed=seed)

    def weights(self) -> Dict[str, TensorRelation]:
        return self._weights

    def step_program(self) -> Dict[str, Expr]:
        if self._program is None:
            c, d, v = self.capacity, self.d, self.vocab
            s = E.input("lm.state", (c, 1), (1, d))
            emb = E.input("lm.emb", (c, 1), (1, d))
            active = E.input("lm.active", (c, 1), (1, 1))
            wh = E.input("lm.Wh", (1, 1), (d, d))
            wx = E.input("lm.Wx", (1, 1), (d, d))
            wo = E.input("lm.Wo", (1, 1), (d, v))
            h = ((s @ wh) + (emb @ wx)).map("relu")
            self._program = {"state": s.slot_update(h, active),
                             "logits": h @ wo}
        return self._program

    def init_state(self) -> TensorRelation:
        c, d = self.capacity, self.d
        return TensorRelation(jnp.zeros((c, 1, 1, d), jnp.float32),
                              self._state_rtype)

    def step_inputs(self, tokens: Sequence[Optional[int]]
                    ) -> Dict[str, TensorRelation]:
        c, d = self.capacity, self.d
        if len(tokens) != c:
            raise ValueError(f"need {c} per-slot tokens, got {len(tokens)}")
        emb = np.zeros((c, 1, 1, d), np.float32)
        mask = np.zeros((c, 1, 1, 1), np.float32)
        for i, t in enumerate(tokens):
            if t is not None:
                emb[i, 0, 0] = self.embedding[int(t)]
                mask[i] = 1.0
        return {"lm.emb": TensorRelation(jnp.asarray(emb),
                                         RelType((c, 1), (1, d))),
                "lm.active": TensorRelation(jnp.asarray(mask),
                                            RelType((c, 1), (1, 1)))}

    def next_token(self, logits_row: np.ndarray) -> int:
        return int(np.argmax(logits_row))

    # -- dense oracle ------------------------------------------------------
    def oracle_step(self, h: jnp.ndarray, token: int
                    ) -> Tuple[jnp.ndarray, np.ndarray]:
        """One dense recurrence step: ``(h', logits)`` for one sequence."""
        wh = self._weights["lm.Wh"].data[0, 0]
        wx = self._weights["lm.Wx"].data[0, 0]
        wo = self._weights["lm.Wo"].data[0, 0]
        h2 = jax.nn.relu(h @ wh + self.embedding[token][None, :] @ wx)
        return h2, np.asarray((h2 @ wo)[0])

    def oracle_decode(self, prompt: Sequence[int], max_new_tokens: int
                      ) -> Tuple[List[int], List[np.ndarray]]:
        """Greedy per-request dense decode: ``(tokens, per-token logits)``.

        The logits list has one entry per *generated* token — the
        reference the continuously batched server must match at 1e-5
        regardless of which slots its neighbours occupied.
        """
        h = jnp.zeros((1, self.d), jnp.float32)
        for t in prompt[:-1]:
            h, _ = self.oracle_step(h, int(t))
        tok = int(prompt[-1])
        out_tokens: List[int] = []
        out_logits: List[np.ndarray] = []
        for _ in range(max_new_tokens):
            h, logits = self.oracle_step(h, tok)
            tok = self.next_token(logits)
            out_tokens.append(tok)
            out_logits.append(logits)
        return out_tokens, out_logits
