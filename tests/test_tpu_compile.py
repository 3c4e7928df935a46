"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The TPU compiler ships with libtpu, so these compile the main path's
Pallas contraction kernel and the whole §5.3 train step at the paper's
speech-100k widths (D=1600, H=100,000, L=10, N=10^4) for one chip of a
``v5e:2x2`` topology.  They catch what interpret mode cannot: tiles the
chip's compiler refuses, and a step that no longer fits 16 GB.

The topology is described inside a fixture, never at import: only one
process at a time may load libtpu, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

D, H, L, N = 1600, 100_000, 10, 10_000
DIMS = (4, 4, 4, 1, N // 4, D // 4, H // 4, L)   # nb db hb lb bn bd bh bl
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means no libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas_on(monkeypatch):
    """The engine asks the backend (the CPU here) whether to use the
    kernel; answer as a TPU would."""
    import repro.kernels.matmul.ops as ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _shape(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("m", [N, 1, 2, 4, 8],
                         ids=["forward", "bucket1", "bucket2", "bucket4",
                              "bucket8"])
def test_matmul_kernel_compiles_at_speech_width(one_chip, m):
    """X·W1 of the train step (m = N) and of each scorer bucket."""
    from repro.kernels.matmul.ops import matmul
    compiled = jax.jit(lambda a, b: matmul(a, b, impl="pallas")).lower(
        _shape(one_chip, m, D), _shape(one_chip, D, H)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _step_args(ce, sharding):
    return [_shape(sharding, *ce.input_rtypes[n].key_shape,
                   *ce.input_rtypes[n].bound) for n in ce.input_names]


def _total_bytes(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)


def test_train_step_fits_one_chip(one_chip, pallas_on):
    """The speech-100k SGD step through ``Engine(executor="jit")``."""
    from repro.core import Engine, SGD
    from repro.core.programs import ffnn_train_step_tra

    step = ffnn_train_step_tra(*DIMS, optimizer=SGD(1e-8))
    ce = Engine(executor="jit").compile(step.roots)
    compiled = ce.jitted.lower(*_step_args(ce, one_chip)).compile()
    assert _total_bytes(compiled) < HBM_BYTES
    assert "tpu_custom_call" in compiled.as_text()


def test_gspmd_dp_step_compiles_for_four_chips(topo, pallas_on):
    """TRA-DP over a ("sites",) mesh of four chips: XLA's partitioner
    cannot split a Mosaic kernel, so gspmd must lower the fused matmul
    to XLA's dot."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import Engine, SGD
    from repro.core.programs import ffnn_dp_placements, ffnn_train_step_tra

    mesh = Mesh(np.array(topo.devices), ("sites",))
    step = ffnn_train_step_tra(*DIMS, optimizer=SGD(1e-8))
    ce = Engine(mesh, executor="gspmd",
                input_placements=ffnn_dp_placements(*DIMS[:4])
                ).compile(step.roots)
    compiled = ce.jitted.lower(
        *_step_args(ce, NamedSharding(mesh, P("sites")))).compile()
    assert _total_bytes(compiled) < HBM_BYTES / 4     # bytes per device
    assert "all-gather" in compiled.as_text()
