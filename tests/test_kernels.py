"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles,
executed in interpret mode on CPU (assignment c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ops import _blockwise_jnp, attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.matmul.kernel import matmul_pallas
from repro.kernels.matmul.ops import matmul
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.kernels.ssd_scan.ops import (ssd_decode_step, ssd_final_state,
                                        ssd_scan)
from repro.kernels.ssd_scan.ref import ssd_ref


# ---------------------------------------------------------------- matmul
@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 512, 128),
                                   (384, 256, 640)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_pallas_interpret(m, k, n, dtype):
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (m, k), dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), dtype)
    out = matmul_pallas(a, b, block_m=128, block_n=128, block_k=128,
                        interpret=True)
    ref = matmul_ref(a, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol * k ** 0.5)


def test_matmul_op_pads_ragged():
    a = jax.random.normal(jax.random.PRNGKey(0), (100, 70))
    b = jax.random.normal(jax.random.PRNGKey(1), (70, 50))
    out = matmul(a, b, impl="pallas", block_m=64, block_n=64, block_k=64,
                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------- flash attention
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("causal,window,softcap", [
    (True, 0, 0.0), (True, 64, 0.0), (True, 0, 30.0), (False, 0, 0.0)])
def test_flash_attention_interpret(hq, hkv, causal, window, softcap):
    b, s, d = 2, 256, 64
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, hq, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, s, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, s, d))
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 softcap=softcap, block_q=128,
                                 block_kv=128, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window,
                        softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    b, h, s, d = 1, 4, 128, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, s, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, h, s, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, h, s, d), dtype)
    out = flash_attention_pallas(q, k, v, causal=True, block_q=64,
                                 block_kv=64, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("sq", [96, 256, 511])
def test_blockwise_jnp_matches_ref(sq):
    b, hq, hkv, d = 2, 8, 4, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, hq, sq, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, sq, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, sq, d))
    for kw in (dict(causal=True, window=0, softcap=0.0),
               dict(causal=True, window=33, softcap=0.0),
               dict(causal=True, window=0, softcap=8.0)):
        out = _blockwise_jnp(q, k, v, scale=None, block_q=128, **kw)
        ref = attention_ref(q, k, v, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_attention_op_decode_path():
    """sq=1 against a longer KV cache (ends-aligned causal)."""
    b, h, skv, d = 2, 4, 64, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, 1, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, h, skv, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, h, skv, d))
    out = attention(q, k, v, causal=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- SSD scan
@pytest.mark.parametrize("s,chunk", [(64, 16), (128, 32), (96, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunked_jnp_vs_ref(s, chunk, dtype):
    b, h, p, n = 2, 4, 16, 8
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                           (b, s, h))).astype(dtype)
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (h,)))
    Bm = jax.random.normal(jax.random.PRNGKey(3), (b, s, n), dtype)
    Cm = jax.random.normal(jax.random.PRNGKey(4), (b, s, n), dtype)
    out = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, impl="jnp")
    ref, _ = ssd_ref(x, dt, A, Bm, Cm)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("s,chunk", [(64, 32), (128, 64)])
def test_ssd_pallas_interpret(s, chunk):
    b, h, p, n = 1, 2, 16, 8
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                           (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (h,)))
    Bm = jax.random.normal(jax.random.PRNGKey(3), (b, s, n))
    Cm = jax.random.normal(jax.random.PRNGKey(4), (b, s, n))
    out = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ref, _ = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_ssd_decode_matches_scan():
    """Recurrent decode steps == full scan, via the prefill state."""
    b, s, h, p, n = 2, 32, 4, 8, 8
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (b, s + 4, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                           (b, s + 4, h)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (h,)))
    Bm = jax.random.normal(jax.random.PRNGKey(3), (b, s + 4, n))
    Cm = jax.random.normal(jax.random.PRNGKey(4), (b, s + 4, n))
    full, _ = ssd_ref(x, dt, A, Bm, Cm)
    hstate = ssd_final_state(x[:, :s], dt[:, :s], A, Bm[:, :s], Cm[:, :s])
    for t in range(s, s + 4):
        y, hstate = ssd_decode_step(hstate, x[:, t], dt[:, t], A,
                                    Bm[:, t], Cm[:, t])
        np.testing.assert_allclose(np.asarray(y), np.asarray(full[:, t]),
                                   rtol=1e-4, atol=1e-4)



def test_matmul_pallas_traces_inside_shard_map():
    """The kernel's output declares the mesh axes its operands vary over,
    which shard_map's type check requires of a pallas_call (traced only:
    interpret mode does not run under that check)."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("sites",))
    f = jax.shard_map(matmul_pallas, mesh=mesh, in_specs=(P("sites"), P()),
                      out_specs=P("sites"))
    a = jax.ShapeDtypeStruct((1024, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 1024), jnp.float32)
    assert jax.eval_shape(f, a, b).shape == (1024, 1024)
