"""The paper's §5.3 two-layer FFNN as the benchmark sees it.

Seeded data and weights, the FLOP and byte counts of a step, and the plain
reference with its lower-precision controls.  Nothing here imports the
program under test: the program is handed the arrays made here, and the
reference makes them again from the seed.

A configuration is a dict read from ``bench/configs/<name>.json``:
``batch`` N, ``d_in`` D, ``d_hidden`` H, ``d_out`` L, the key ``grid``
(nb, db, hb, lb) over (N, D, H, L), ``x_scale``, ``lr`` and ``labels``.

Arrays are made directly in the program's relation layout: a matrix of
key grid (k0, k1) and block (b0, b1) is the array ``(k0, k1, b0, b1)``.
:func:`dense` turns such an array back into the plain matrix.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MAX_SEED = 2 ** 64


# -- shapes -----------------------------------------------------------------

def sizes(cfg) -> Tuple[int, int, int, int]:
    """(N, D, H, L)."""
    return cfg["batch"], cfg["d_in"], cfg["d_hidden"], cfg["d_out"]


def block_dims(cfg) -> Tuple[int, ...]:
    """(nb, db, hb, lb, bn, bd, bh, bl): the key grid and the block sizes."""
    grid = tuple(cfg["grid"])
    full = sizes(cfg)
    if len(grid) != 4 or any(s % g for s, g in zip(full, grid)):
        raise ValueError(f"grid {grid} does not divide {full}")
    return grid + tuple(s // g for s, g in zip(full, grid))


def step_flops(cfg) -> int:
    """Model FLOPs of one SGD step: X·W1 and a1·W2 forward, a1ᵀ·d2,
    d2·W2ᵀ and Xᵀ·dA1 backward (no dX), 2 per multiply-add."""
    n, d, h, l = sizes(cfg)
    return 4 * n * d * h + 6 * n * h * l


def step_contractions(cfg) -> List[Tuple[str, int, int, int]]:
    """The step's matrix products as (name, m, k, n), unpadded."""
    n, d, h, l = sizes(cfg)
    return [("X.W1", n, d, h), ("a1.W2", n, h, l), ("a1T.d2", h, n, l),
            ("d2.W2T", n, l, h), ("XT.dA1", d, n, h)]


def matmul_bytes(m: int, k: int, n: int, itemsize: int = 4) -> int:
    """Least HBM traffic of one product: read both operands, write C."""
    return itemsize * (m * k + k * n + m * n)


# -- seeded data ------------------------------------------------------------

def seed_words(seed: int) -> Tuple[np.uint32, np.uint32]:
    """A seed of up to 64 bits as two 32-bit words (low, high)."""
    if not 0 <= seed < MAX_SEED:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def _base_key(lo, hi):
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _weights(cfg, base):
    nb, db, hb, lb, bn, bd, bh, bl = block_dims(cfg)
    _, d, h, _ = sizes(cfg)
    xs = cfg["x_scale"]
    k1, k2 = jax.random.split(jax.random.fold_in(base, 0))
    w1 = jax.random.normal(k1, (db, hb, bd, bh), jnp.float32) * (
        d ** -0.5 / xs)
    w2 = jax.random.normal(k2, (hb, lb, bh, bl), jnp.float32) * h ** -0.5
    return w1, w2


def _features(cfg, base, j: int):
    nb, db, _, _, bn, bd, _, _ = block_dims(cfg)
    return jax.random.normal(jax.random.fold_in(base, 1 + j),
                             (nb, db, bn, bd), jnp.float32) * cfg["x_scale"]


def _labels(cfg, base, k: int, x):
    """Labels of minibatch ``k`` in layout (nb, lb, bn, bl).

    ``teacher``: the sigmoid of a random linear teacher of ``x``, scaled
    so its logits have unit size.  ``sparse``: independent 0/1 labels with
    ``per_row`` positives per row on average."""
    nb, db, _, lb, bn, bd, _, bl = block_dims(cfg)
    _, d, _, l = sizes(cfg)
    key = jax.random.fold_in(base, 1000 + k)
    spec = cfg["labels"]
    if spec["kind"] == "teacher":
        t = jax.random.normal(key, (db, bd, l), jnp.float32) * (
            d ** -0.5 / cfg["x_scale"])
        y = jax.nn.sigmoid(jnp.einsum("abij,bjl->ail", x, t,
                                      precision=HIGHEST))
        return y.reshape(nb, bn, lb, bl).transpose(0, 2, 1, 3)
    if spec["kind"] == "sparse":
        u = jax.random.uniform(key, (nb, lb, bn, bl), jnp.float32)
        return (u < spec["per_row"] / l).astype(jnp.float32)
    raise ValueError(f"unknown label kind {spec['kind']!r}")


def minibatch_count(cfg, minibatches: int) -> int:
    """How many distinct feature sets back ``minibatches`` minibatches."""
    return min(minibatches, cfg.get("feature_sets", minibatches))


def make_train_data(cfg, seed: int, minibatches: int, shardings=None):
    """W1, W2 and ``minibatches`` (X, Y) pairs, made in one jitted call:
    on the default device, or laid out as ``shardings`` where given:
    (W1's, W2's, every X's, every Y's).  Minibatch k has
    labels of its own and the features of set ``k % feature_sets``."""
    fsets = minibatch_count(cfg, minibatches)

    def make(lo, hi):
        base = _base_key(lo, hi)
        w1, w2 = _weights(cfg, base)
        xs = [_features(cfg, base, j) for j in range(fsets)]
        ys = [_labels(cfg, base, k, xs[k % fsets]) for k in range(minibatches)]
        return w1, w2, xs, ys

    if shardings is None:
        return jax.jit(make)(*seed_words(seed))
    w1, w2, x, y = shardings
    out = (w1, w2, [x] * fsets, [y] * minibatches)
    return jax.jit(make, out_shardings=out)(*seed_words(seed))


def make_weights(cfg, seed: int):
    """W1 and W2 alone, in relation layout."""
    return jax.jit(lambda lo, hi: _weights(cfg, _base_key(lo, hi)))(
        *seed_words(seed))


def make_requests(cfg, seed: int, n: int) -> np.ndarray:
    """``n`` scoring requests, one D-feature vector each, on the host."""
    rng = np.random.default_rng([seed, 1])
    _, d, _, _ = sizes(cfg)
    x = rng.standard_normal((n, d), dtype=np.float32)
    x *= np.float32(cfg["x_scale"])
    return x


def dense(a):
    """(k0, k1, b0, b1) relation layout → the (k0·b0, k1·b1) matrix."""
    k0, k1, b0, b1 = a.shape
    return a.transpose(0, 2, 1, 3).reshape(k0 * b0, k1 * b1)


# -- the plain reference ----------------------------------------------------

def _int8_matmul(a, b):
    """``a @ b`` on int8 operands: symmetric scales per row of ``a`` and
    per column of ``b``, int32 accumulation, float32 result."""
    sa = jnp.max(jnp.abs(a), axis=1, keepdims=True) / 127.0
    sb = jnp.max(jnp.abs(b), axis=0, keepdims=True) / 127.0
    qa = jnp.round(a / jnp.where(sa > 0, sa, 1.0)).astype(jnp.int8)
    qb = jnp.round(b / jnp.where(sb > 0, sb, 1.0)).astype(jnp.int8)
    acc = jnp.dot(qa, qb, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sa * sb


def precision_ops(precision: str):
    """(cast, matmul) for one arithmetic: ``float32`` at "highest" (the
    reference), ``bfloat16`` throughout, ``int8`` contraction operands
    with float32 elsewhere, or ``bfloat16_operands``: one bfloat16 pass
    with float32 accumulation and float32 elsewhere, the arithmetic the
    configurations state for the program."""
    if precision == "float32":
        return (lambda a: a.astype(jnp.float32),
                lambda a, b: jnp.dot(a, b, precision=HIGHEST))
    if precision == "bfloat16":
        return (lambda a: a.astype(jnp.bfloat16),
                lambda a, b: jnp.dot(a, b))
    if precision == "int8":
        return (lambda a: a.astype(jnp.float32), _int8_matmul)
    if precision == "bfloat16_operands":
        return (lambda a: a.astype(jnp.float32),
                lambda a, b: jnp.dot(a.astype(jnp.bfloat16),
                                     b.astype(jnp.bfloat16),
                                     preferred_element_type=jnp.float32))
    raise ValueError(f"unknown precision {precision!r}")


def _bce(a2, y):
    p = jnp.clip(a2.astype(jnp.float32), 1e-7, 1.0 - 1e-7)
    return jnp.sum(-(y * jnp.log(p) + (1.0 - y) * jnp.log1p(-p)))


def _train_step_fn(cfg, precision: str):
    """One SGD step of the plain model.  Returns (loss, W1', W2')."""
    cast, mm = precision_ops(precision)
    lr = cfg["lr"]

    def step(x, y, w1, w2):
        x = cast(x)
        a1 = jax.nn.relu(mm(x, w1))
        a2 = jax.nn.sigmoid(mm(a1, w2))
        d2 = (a2.astype(jnp.float32) - y).astype(a2.dtype)
        g2 = mm(a1.T, d2).astype(w2.dtype)
        da1 = mm(d2, w2.T) * (a1 > 0)
        g1 = mm(x.T, da1).astype(w1.dtype)
        return (_bce(a2, y), (w1 - lr * g1).astype(w1.dtype),
                (w2 - lr * g2).astype(w2.dtype))

    return jax.jit(step)


def _blocked_step_fn(cfg, precision: str, rows: int, sites: int = 0):
    """One SGD step of the plain model over the batch in blocks of
    ``rows`` rows, the gradient summed over the blocks, so that no
    activation of the whole batch is alive at once.  Returns
    (loss, W1', W2').

    With ``sites``, the batch falls into ``sites`` equal blocks instead,
    and each site's stored share of W1 and of W2 (a contiguous
    ``1/sites`` of their rows, as TRA-DP stores them) takes the gradient
    of that site's block alone: the exchange of the gradients between
    the sites left out."""
    cast, mm = precision_ops(precision)
    lr = cfg["lr"]

    @jax.jit
    def grads(x, y, w1, w2):
        x = cast(x)
        a1 = jax.nn.relu(mm(x, w1))
        a2 = jax.nn.sigmoid(mm(a1, w2))
        d2 = (a2.astype(jnp.float32) - y).astype(a2.dtype)
        g2 = mm(a1.T, d2).astype(w2.dtype)
        da1 = mm(d2, w2.T) * (a1 > 0)
        g1 = mm(x.T, da1).astype(w1.dtype)
        return _bce(a2, y), g1, g2

    @jax.jit
    def update(w, g):
        return (w - lr * g).astype(w.dtype)

    def own_rows(g, site):
        """``g`` with the rows outside ``site``'s share zeroed."""
        owner = jnp.arange(g.shape[0]) * sites // g.shape[0]
        return jnp.where((owner == site)[:, None], g, 0)

    def step(x, y, w1, w2):
        n = x.shape[0]
        size = n // sites if sites else rows
        loss, g1, g2 = 0.0, 0, 0
        for i, lo in enumerate(range(0, n, size)):
            l, a, b = grads(x[lo:lo + size], y[lo:lo + size], w1, w2)
            if sites:
                a, b = own_rows(a, i), own_rows(b, i)
            loss, g1, g2 = loss + l, g1 + a, g2 + b
        return loss, update(w1, g1), update(w2, g2)

    return step


def _norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


def reference_train(cfg, seed: int, minibatches: int, steps: int, *,
                    precision: str = "float32",
                    fault: str = "") -> Dict[str, object]:
    """The first ``steps`` SGD steps of the plain model from ``seed``.

    Returns the loss of each step, each leaf's first gradient norm as
    SGD got it, ``‖W0 − W1‖/lr`` (worked out from the state, as the
    program's is), and each leaf's norm of change after the last step,
    as host floats.
    ``fault`` plants a fault in the reference put in the program's
    place.  ``half_batch``: the second half of every batch is replaced by
    the first, so the sum runs over half of the batch, doubled.
    ``no_exchange``: each of the configuration's ``mesh`` sites updates
    its stored share of the weights with the gradient of its own share
    of the batch alone.

    A configuration with ``reference_rows_per_block`` runs the step over
    the batch in blocks of that many rows."""
    w1, w2, xs, ys = make_train_data(cfg, seed, minibatches)
    cast, _ = precision_ops(precision)
    # one array at a time, so that a layout and its dense copy are the
    # only two copies of it alive
    w1 = cast(dense(w1))
    w2 = cast(dense(w2))
    xs = [dense(xs.pop(0)) for _ in range(len(xs))]
    ys = [dense(y) for y in ys]
    rows = cfg.get("reference_rows_per_block")
    if fault == "no_exchange":
        sites = math.prod(cfg["mesh"]["shape"])
        step = _blocked_step_fn(cfg, precision, rows, sites)
    elif rows:
        step = _blocked_step_fn(cfg, precision, rows)
    else:
        step = _train_step_fn(cfg, precision)
    losses, grad_norms = [], None
    for k in range(steps):
        x, y = xs[k % len(xs)], ys[k % minibatches]
        if fault == "half_batch":
            h = x.shape[0] // 2
            x = jnp.concatenate([x[:h], x[:h]])
            y = jnp.concatenate([y[:h], y[:h]])
        elif fault not in ("", "no_exchange"):
            raise ValueError(f"unknown fault {fault!r}")
        loss, w1, w2 = step(x, y, w1, w2)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {k: v / cfg["lr"] for k, v in change_norms(
                cfg, seed, {"W1": w1, "W2": w2}, layout="dense").items()}
        del x, y
    del xs, ys
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms(cfg, seed, {"W1": w1, "W2": w2},
                                         layout="dense")}


def change_norms(cfg, seed: int, params, layout: str) -> Dict[str, float]:
    """‖W − W0‖ of each leaf, W0 made again from ``seed``; ``params`` are
    in relation ``layout`` or ``dense``."""
    lo, hi = seed_words(seed)

    def gap(lo, hi, w1, w2):
        w1_0, w2_0 = _weights(cfg, _base_key(lo, hi))
        if layout == "dense":
            w1_0, w2_0 = dense(w1_0), dense(w2_0)
        return (_norm(w1.astype(jnp.float32) - w1_0),
                _norm(w2.astype(jnp.float32) - w2_0))

    n1, n2 = jax.jit(gap)(lo, hi, params["W1"], params["W2"])
    return {"W1": float(n1), "W2": float(n2)}


def reference_scores(cfg, seed: int, requests: np.ndarray, *,
                     precision: str = "float32",
                     rows_per_block: int = 2048) -> np.ndarray:
    """Scores of the plain forward pass for every row of ``requests``."""
    w1b, w2b = make_weights(cfg, seed)
    cast, mm = precision_ops(precision)
    w1, w2 = cast(dense(w1b)), cast(dense(w2b))
    del w1b, w2b

    @jax.jit
    def fwd(x, w1, w2):
        a1 = jax.nn.relu(mm(cast(x), w1))
        # the scores leave in the arithmetic's own type: a bfloat16 score
        # converted inside the program would let XLA keep it in float32
        return jax.nn.sigmoid(mm(a1, w2))

    out = []
    for i in range(0, len(requests), rows_per_block):
        blk = requests[i:i + rows_per_block]
        pad = rows_per_block - len(blk)      # one shape, one compile
        if pad:
            blk = np.concatenate([blk, np.zeros((pad, blk.shape[1]),
                                                blk.dtype)])
        scores = np.asarray(fwd(jnp.asarray(blk), w1, w2)).astype(np.float32)
        out.append(scores[:rows_per_block - pad])
    return np.concatenate(out) if out else np.zeros((0, sizes(cfg)[3]))


def norm_gap(got: Dict[str, float], want: Dict[str, float]) -> float:
    """Worst leaf of |‖got‖ − ‖want‖| over the larger of that leaf's
    reference norm and the median leaf's.  Leaves whose reference norm is
    under a thousandth of the median leaf's are left out."""
    med = float(np.median(list(want.values())))
    worst = 0.0
    for name, w in want.items():
        if w < 1e-3 * med:
            continue
        worst = max(worst, abs(got[name] - w) / max(w, med))
    return worst


def loss_gap(got: Sequence[float], want: Sequence[float]) -> float:
    """Largest relative gap of the per-step losses."""
    if len(got) != len(want):
        return math.inf
    return max(abs(g - w) / abs(w) for g, w in zip(got, want))


# -- the numbers compared ------------------------------------------------------

def train_numbers(program, ref) -> Dict[str, float]:
    """What decides a train cell's ``correct``: the per-step loss, each
    leaf's first gradient norm and each leaf's norm of change."""
    return {"loss_gap": loss_gap(program["losses"], ref["losses"]),
            "grad_gap": norm_gap(program["grad_norms"], ref["grad_norms"]),
            "change_gap": norm_gap(program["change_norms"],
                                   ref["change_norms"])}


def score_numbers(got: np.ndarray, want: np.ndarray,
                  stated: np.ndarray) -> Dict[str, float]:
    """What decides a scoring cell's ``correct``: the widest gap of any
    score of any answer from the float32 reference's (``score_gap``), and
    from the reference's at the configuration's stated precision
    (``stated_gap``), which a lower precision anywhere on the path
    departs from."""
    def widest(ref):
        if got.shape != ref.shape:
            return math.inf
        return float(np.max(np.abs(got - ref))) if got.size else 0.0
    return {"score_gap": widest(want), "stated_gap": widest(stated)}
