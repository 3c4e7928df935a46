"""Fault-tolerant TRA execution (the robustness tentpole).

Covers the ISSUE-6 acceptance criteria at tier-1 scale (single device;
the 8-device elastic re-mesh resume lives in
``tests/_distributed_checks.py`` behind the ``slow`` marker):

* a ``TraTrainer`` run killed mid-``fit`` by an injected
  ``SimulatedFailure`` recovers from the last committed checkpoint and —
  including when a *fresh* trainer resumes in a "new process" — matches
  the uninterrupted oracle's per-step losses at 1e-5;
* injected device OOM in the fused contraction completes via the halving
  streamed-chunk backoff ladder with correct results on every executor;
* ``check_numerics`` attributes an injected (and a data-borne) NaN to
  the exact TRA node that produced it;
* the executor compile-failure fallback ladder degrades with one
  ``RuntimeWarning`` and never shadows a later successful compile of the
  preferred executor (degraded artifacts are cached under their own key);
* ``CheckpointStore.save_async`` surfaces background-write failures on
  the next ``wait()``/``save_async()`` (regression for the silent-swallow
  bug);
* the trainer's bounded skip-step policy for non-finite losses.

Everything here is deterministic: faults are scripted on a
``FaultInjector`` and keyed on plan-signature node ids / run indices.
"""
import warnings

import jax
import numpy as np
import pytest

import repro.core as tra
from repro.core import (AdamW, Engine, TensorRelation, TraTrainer,
                        from_tensor)
from repro.core.engine import DEFAULT_OOM_LADDER_START
from repro.core.faults import (CompileFailure, DeviceOOM, FaultInjector,
                               SimulatedFailure)
from repro.core.guards import NumericsError, label_nodes
from repro.core.plan import as_node
from repro.core.programs import ffnn_train_step_tra
from repro.checkpoint import CheckpointStore

pytestmark = pytest.mark.faults

S = ("sites",)
DIMS = (4, 2, 2, 2, 4, 4, 4, 2)


def _mesh1():
    from repro.launch.mesh import make_mesh
    return make_mesh((1,), S)


def _bmm_expr():
    A = tra.input("A", key_shape=(4, 3), bound=(2, 2))
    B = tra.input("B", key_shape=(3, 5), bound=(2, 2))
    return A @ B


def _bmm_data(nan_in_a=False):
    rng = np.random.default_rng(0)
    A = rng.normal(size=(4, 3, 2, 2)).astype(np.float32)
    B = rng.normal(size=(3, 5, 2, 2)).astype(np.float32)
    if nan_in_a:
        A[1, 2, 0, 1] = np.nan
    return A, B


def _train_fixture():
    nb, db, hb, lb, bn, bd, bh, bl = DIMS
    X = jax.random.normal(jax.random.PRNGKey(0), (nb * bn, db * bd))
    Wt = jax.random.normal(jax.random.PRNGKey(4), (db * bd, lb * bl)) * 0.5
    Y = jax.nn.sigmoid(X @ Wt)
    W1 = jax.random.normal(jax.random.PRNGKey(2), (db * bd, hb * bh)) * 0.3
    W2 = jax.random.normal(jax.random.PRNGKey(3), (hb * bh, lb * bl)) * 0.3
    data = dict(X=from_tensor(X, (bn, bd)), Y=from_tensor(Y, (bn, bl)))

    def params():
        return dict(W1=from_tensor(W1, (bd, bh)),
                    W2=from_tensor(W2, (bh, bl)))

    def trainer(engine, **kw):
        return TraTrainer(engine, ffnn_train_step_tra(
            *DIMS, optimizer=AdamW(1e-2)), params=params(), **kw)

    return data, trainer


# ==========================================================================
# Checkpoint / resume
# ==========================================================================

def test_kill_midrun_resumes_and_matches_oracle(tmp_path):
    """SimulatedFailure at run 5 → auto-recovery from the last committed
    step; a FRESH trainer (new engine) then resumes to 8 total steps and
    the full trajectory matches the uninterrupted oracle at 1e-5."""
    data, trainer = _train_fixture()
    oracle = trainer(Engine(executor="jit", optimize=False)).fit(8, **data)

    store = CheckpointStore(str(tmp_path / "ckpt"), keep=5)
    inj = FaultInjector().inject_site_failure(step=5)
    tr = trainer(Engine(executor="jit", optimize=False, fault_injector=inj),
                 store=store)
    h = tr.fit(6, ckpt_every=2, **data)
    assert inj.log == [("site", "run 5")]
    assert len(h) == 6 and tr.step_count == 6
    np.testing.assert_allclose(h, oracle[:6], atol=1e-5)

    tr2 = trainer(Engine(executor="jit", optimize=False), store=store)
    h2 = tr2.fit(8, resume=True, **data)
    assert tr2.step_count == 8
    np.testing.assert_allclose(h2, oracle, atol=1e-5)


def test_resume_on_empty_store_starts_fresh(tmp_path):
    data, trainer = _train_fixture()
    store = CheckpointStore(str(tmp_path / "ckpt"))
    tr = trainer(Engine(executor="jit", optimize=False), store=store)
    h = tr.fit(3, resume=True, ckpt_every=2, **data)
    assert len(h) == 3 and tr.step_count == 3
    assert store.latest_step() is not None


def test_failure_before_first_periodic_checkpoint_recovers(tmp_path):
    """fit commits the initial state, so a kill before the first periodic
    snapshot restores to step 0 instead of crashing unrecoverably."""
    data, trainer = _train_fixture()
    oracle = trainer(Engine(executor="jit", optimize=False)).fit(3, **data)
    store = CheckpointStore(str(tmp_path / "ckpt"))
    inj = FaultInjector().inject_site_failure(step=1)
    tr = trainer(Engine(executor="jit", optimize=False, fault_injector=inj),
                 store=store)
    h = tr.fit(3, ckpt_every=10, **data)
    np.testing.assert_allclose(h, oracle, atol=1e-5)


def test_unrecoverable_without_store():
    data, trainer = _train_fixture()
    inj = FaultInjector().inject_site_failure(step=1)
    tr = trainer(Engine(executor="jit", optimize=False, fault_injector=inj))
    with pytest.raises(SimulatedFailure):
        tr.fit(4, **data)


def test_store_async_write_failure_surfaces(tmp_path, monkeypatch):
    """Regression: a failed background write must raise on the next
    wait()/save_async(), never be silently swallowed."""
    store = CheckpointStore(str(tmp_path / "ckpt"))

    def boom(step, leaves, treedef, extra):
        raise OSError("injected I/O error: disk full")

    monkeypatch.setattr(store, "_write", boom)
    store.save_async(1, {"w": np.zeros(3)})
    with pytest.raises(OSError, match="disk full"):
        store.wait()
    # the error is consumed once — the store is usable again
    monkeypatch.undo()
    store.save_async(2, {"w": np.zeros(3)})
    store.wait()
    assert store.latest_step() == 2

    # surfaced by the next save_async too (not only explicit wait)
    monkeypatch.setattr(store, "_write", boom)
    store.save_async(3, {"w": np.zeros(3)})
    with pytest.raises(OSError, match="disk full"):
        store.save_async(4, {"w": np.zeros(3)})


# ==========================================================================
# Numeric guards with plan provenance
# ==========================================================================

@pytest.mark.parametrize("executor", ["reference", "jit"])
def test_injected_nan_attributed_to_exact_node(executor):
    """check_numerics names the first TRA node that produced the NaN —
    here the fused Σ∘⋈ contraction the optimizer selected."""
    inj = FaultInjector().inject_nan(node="FusedJoinAgg", times=-1)
    eng = Engine(executor=executor, fault_injector=inj, check_numerics=True)
    A, B = _bmm_data()
    with pytest.raises(NumericsError) as ei:
        eng.run(_bmm_expr(), A=A, B=B)
    assert "FusedJoinAgg" in str(ei.value)
    assert ei.value.node_label is not None
    # the label carries the plan-signature node id prefix ("2:FusedJoinAgg…")
    nid = int(ei.value.node_label.split(":")[0])
    assert nid >= 0


@pytest.mark.parametrize("executor", ["reference", "jit"])
def test_data_borne_nan_attributed_to_input_node(executor):
    """A NaN arriving IN the data (no injector) is attributed to the input
    node — postorder checking names the producer, not a consumer."""
    eng = Engine(executor=executor, check_numerics=True)
    A, B = _bmm_data(nan_in_a=True)
    with pytest.raises(NumericsError) as ei:
        eng.run(_bmm_expr(), A=A, B=B)
    assert "Input[A]" in str(ei.value)


@pytest.mark.parametrize("executor", ["gspmd", "shard_map"])
def test_distributed_executors_check_outputs(executor):
    """The distributed executors get output-level finite checks (per-node
    probes would perturb the collective schedule under test)."""
    eng = Engine(_mesh1(), executor=executor, check_numerics=True)
    A, B = _bmm_data(nan_in_a=True)
    with pytest.raises(NumericsError, match="output"):
        eng.run(_bmm_expr(), A=A, B=B)


def test_check_numerics_off_is_silent():
    A, B = _bmm_data(nan_in_a=True)
    out = Engine(executor="jit").run(_bmm_expr(), A=A, B=B)
    assert np.isnan(np.asarray(out.data)).any()


def test_check_numerics_all_mode_attributes_in_primary_program():
    """check_numerics="all" carries the per-node flags in the primary jit
    program (no attribution re-run) and names the same exact node the
    default two-tier mode finds."""
    A, B = _bmm_data()
    labels = {}
    for mode in (True, "all"):
        inj = FaultInjector().inject_nan(node="FusedJoinAgg", times=-1)
        eng = Engine(executor="jit", fault_injector=inj,
                     check_numerics=mode)
        with pytest.raises(NumericsError) as ei:
            eng.run(_bmm_expr(), A=A, B=B)
        assert "FusedJoinAgg" in str(ei.value)
        labels[mode] = ei.value.node_label
    assert labels[True] == labels["all"]


def test_skip_step_policy_matches_oracle_and_bounds():
    """Two scoped NaN steps are skipped without advancing params/state;
    the applied trajectory equals the oracle.  An unbounded NaN stream
    exhausts the consecutive-skip budget and raises."""
    data, trainer = _train_fixture()
    oracle = trainer(Engine(executor="reference", optimize=False)) \
        .fit(4, **data)

    inj = FaultInjector() \
        .inject_nan(node="TraAgg", times=1) \
        .inject_nan(node="TraAgg", times=1)
    inj._faults[0].step = 1
    inj._faults[1].step = 2
    eng = Engine(executor="reference", optimize=False, fault_injector=inj,
                 check_numerics=True)
    tr = trainer(eng, skip_nonfinite=3)
    h = tr.fit(4, **data)
    assert len(tr.skipped) == 2
    np.testing.assert_allclose(h, oracle, atol=1e-5)

    inj2 = FaultInjector().inject_nan(node="TraAgg", times=-1)
    eng2 = Engine(executor="reference", optimize=False, fault_injector=inj2,
                  check_numerics=True)
    tr2 = trainer(eng2, skip_nonfinite=2)
    with pytest.raises(NumericsError, match="consecutive non-finite"):
        tr2.fit(4, **data)
    assert tr2.step_count == 0          # params never advanced


# ==========================================================================
# Graceful degradation: OOM chunk ladder + executor fallback
# ==========================================================================

@pytest.mark.parametrize("executor", ["reference", "jit", "gspmd",
                                      "shard_map"])
def test_oom_ladder_completes_on_all_executors(executor):
    """Injected device OOM (fits only at streaming chunk <= 2) degrades
    through the halving ladder and completes with correct results."""
    mesh = _mesh1() if executor in ("gspmd", "shard_map") else None
    A, B = _bmm_data()
    base = Engine(executor="reference").run(_bmm_expr(), A=A, B=B).data

    inj = FaultInjector().inject_oom(ok_chunk=2)
    eng = Engine(mesh, executor=executor, fault_injector=inj, degrade=True)
    with pytest.warns(RuntimeWarning, match="streamed"):
        out = eng.run(_bmm_expr(), A=A, B=B).data
    np.testing.assert_allclose(np.asarray(out), np.asarray(base), atol=1e-4)
    # the ladder actually walked: unstreamed attempt plus halving chunks
    ooms = [d for k, d in inj.log if k == "oom"]
    assert any("unstreamed" in d for d in ooms)
    assert any(f"chunk={DEFAULT_OOM_LADDER_START}" in d for d in ooms)


def test_oom_propagates_without_degrade():
    inj = FaultInjector().inject_oom(ok_chunk=2)
    eng = Engine(executor="jit", fault_injector=inj)
    A, B = _bmm_data()
    with pytest.raises(DeviceOOM):
        eng.run(_bmm_expr(), A=A, B=B)


def test_compile_fallback_warns_and_is_not_shadowed():
    """Satellite: a degraded artifact is cached under the fallback key, so
    the preferred executor is retried and a later successful compile is
    not shadowed by the degraded entry."""
    inj = FaultInjector().inject_compile_failure(executor="jit", times=1)
    eng = Engine(executor="jit", fault_injector=inj, degrade=True)
    A, B = _bmm_data()
    base = Engine(executor="reference").run(_bmm_expr(), A=A, B=B).data

    with pytest.warns(RuntimeWarning, match="degraded to executor"):
        c1 = eng.compile(_bmm_expr())
    assert c1.executor == "reference" and c1.degraded_from == "jit"
    np.testing.assert_allclose(np.asarray(c1.run(A=A, B=B).data),
                               np.asarray(base), atol=1e-5)

    # fault budget spent → the preferred executor compiles cleanly now
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        c2 = eng.compile(_bmm_expr())
    assert c2.executor == "jit" and c2.degraded_from is None


def test_distributed_compile_fallback_ladder():
    """gspmd → jit rung of the ladder (single-device mesh)."""
    inj = FaultInjector().inject_compile_failure(executor="gspmd", times=1)
    eng = Engine(_mesh1(), executor="gspmd", fault_injector=inj,
                 degrade=True)
    with pytest.warns(RuntimeWarning, match="degraded to executor 'jit'"):
        c = eng.compile(_bmm_expr())
    assert c.executor == "jit" and c.degraded_from == "gspmd"


def test_compile_failure_propagates_without_degrade():
    inj = FaultInjector().inject_compile_failure(executor="jit", times=1)
    eng = Engine(executor="jit", fault_injector=inj)
    with pytest.raises(CompileFailure):
        eng.compile(_bmm_expr())


def test_user_errors_never_degrade():
    """ValueError (user error) must propagate, not walk the ladder."""
    eng = Engine(executor="jit", degrade=True)
    with pytest.raises(ValueError, match="chunk must be >= 1"):
        eng.compile(_bmm_expr(), chunk=0)


# ==========================================================================
# Injector mechanics
# ==========================================================================

def test_straggler_delays_but_succeeds():
    inj = FaultInjector().inject_straggler(step=1, delay=0.01)
    eng = Engine(executor="jit", fault_injector=inj)
    A, B = _bmm_data()
    eng.run(_bmm_expr(), A=A, B=B)
    eng.run(_bmm_expr(), A=A, B=B)      # delayed, not failed
    assert inj.log == [("straggler", "run 1 +0.01s")]
    assert inj.runs == 2


def test_node_ids_match_plan_signature_postorder():
    """label_nodes numbering is the plan_sig postorder (shared subtrees
    numbered once, multi-root numbering continues across roots)."""
    A = tra.input("A", key_shape=(2, 2), bound=(3, 3))
    B = tra.input("B", key_shape=(2, 2), bound=(3, 3))
    shared = A @ B
    r1, r2 = as_node(shared + A), as_node(shared)
    labels = label_nodes((r1, r2))
    nids = sorted(nid for nid, _ in labels.values())
    assert nids == list(range(len(labels)))     # dense, deduped
    # the shared subtree keeps its first-root id in the second root
    assert labels[id(r2)][0] < len(labels)
    by_label = {lab for _, lab in labels.values()}
    assert any("TraInput[A]" in lab for lab in by_label)


def test_fault_budget_times_is_respected():
    inj = FaultInjector().inject_site_failure(step=0, times=1)
    eng = Engine(executor="jit", fault_injector=inj)
    A, B = _bmm_data()
    with pytest.raises(SimulatedFailure):
        eng.run(_bmm_expr(), A=A, B=B)
    # budget spent; same run index logic never refires
    out = eng.run(_bmm_expr(), A=A, B=B)
    assert out.data.shape == (4, 5, 2, 2)


def _runtime_error(message: str) -> BaseException:
    """A real ``jax.errors.JaxRuntimeError``: a host callback that raises
    inside a jitted program fails the execution with one."""
    def fail(x):
        raise RuntimeError(message)

    f = jax.jit(lambda x: jax.pure_callback(
        fail, jax.ShapeDtypeStruct((2,), np.float32), x))
    with pytest.raises(jax.errors.JaxRuntimeError) as info:
        f(np.ones(2, np.float32)).block_until_ready()
    return info.value


def test_real_runtime_errors_are_classified():
    """Regression: the classifiers once matched the class name
    ``XlaRuntimeError``, which JAX's runtime errors no longer carry, so a
    real device fault was neither retried nor degraded."""
    from repro.core.engine import _is_compile_class
    from repro.core.faults import is_transient
    from repro.core.guards import is_oom_error

    err = _runtime_error("device failure")
    assert is_transient(err)
    assert _is_compile_class(err)
    assert not is_transient(RuntimeError("device failure"))
    assert not _is_compile_class(RuntimeError("device failure"))

    assert is_oom_error(_runtime_error("RESOURCE_EXHAUSTED: allocating"))
    # the class, not the text, makes a "memory" failure an OOM
    assert is_oom_error(_runtime_error("could not reserve device memory"))
    assert not is_oom_error(RuntimeError("could not reserve device memory"))
