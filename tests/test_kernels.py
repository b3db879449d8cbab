"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.kernels.checksum.kernel import checksum_pallas
from repro.kernels.checksum.ref import checksum_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba_scan.kernel import mamba_scan_pallas
from repro.kernels.mamba_scan.ref import mamba_scan_ref
from repro.kernels.rs_encode import gf
from repro.kernels.rs_encode.kernel import rs_encode_pallas
from repro.kernels.rs_encode.ref import rs_encode_np


# ---------------------------------------------------------------------------
# rs_encode


@pytest.mark.parametrize("k,p", [(8, 2), (4, 2), (10, 4), (6, 3)])
@pytest.mark.parametrize("n", [4096, 16384])
@pytest.mark.slow
def test_rs_encode_sweep(k, p, n):
    rng = np.random.default_rng(k * 100 + p)
    data = rng.integers(0, 256, (k, n), dtype=np.uint8)
    gm = gf.generator_matrix(k, p)
    bp = jnp.asarray(gf.bitplane_matrix(gm))
    got = rs_encode_pallas(jnp.asarray(data), bp, block=4096)
    want = rs_encode_np(data, gm)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_rs_zero_data_gives_zero_parity():
    gm = gf.generator_matrix(8, 2)
    bp = jnp.asarray(gf.bitplane_matrix(gm))
    out = rs_encode_pallas(jnp.zeros((8, 4096), jnp.uint8), bp)
    assert not np.asarray(out).any()


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 255), st.integers(0, 255), st.integers(0, 255))
def test_gf_field_axioms(a, b, c):
    m = gf.gf_mul
    assert m(a, b) == m(b, a)
    assert m(a, m(b, c)) == m(m(a, b), c)
    assert m(a, b ^ c) == m(a, b) ^ m(a, c)      # distributivity over XOR
    if a:
        assert m(a, gf.gf_inv(a)) == 1


# ---------------------------------------------------------------------------
# checksum


@pytest.mark.parametrize("B,L", [(1, 64), (7, 128), (32, 512), (9, 1500)])
def test_checksum_sweep(B, L):
    L = L + (L % 2)
    rng = np.random.default_rng(B * L)
    data = rng.integers(0, 256, (B, L), dtype=np.uint8)
    length = rng.integers(0, L + 1, (B,), dtype=np.int32)
    got = checksum_pallas(jnp.asarray(data), jnp.asarray(length))
    want = checksum_ref(jnp.asarray(data), jnp.asarray(length))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=10, deadline=None)
@given(st.binary(min_size=0, max_size=200))
def test_checksum_property_verifies_to_zero(data):
    """Appending the checksum makes the ones-complement sum verify."""
    from repro.net.bytesops import np_checksum16
    cs = np_checksum16(data)
    padded = data + (b"\x00" if len(data) % 2 else b"") + bytes(
        [cs >> 8, cs & 0xFF])
    assert np_checksum16(padded) == 0


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("S,hd,kv,g,window", [
    (256, 64, 2, 1, 0), (512, 128, 1, 4, 0), (256, 64, 2, 2, 128),
    (512, 64, 4, 2, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.slow
def test_flash_attention_sweep(S, hd, kv, g, window, dtype):
    B = 2
    key = jax.random.key(S + hd)
    q = (jax.random.normal(key, (B * kv * g, S, hd)) * 0.5).astype(dtype)
    k = (jax.random.normal(jax.random.fold_in(key, 1),
                           (B * kv, S, hd)) * 0.5).astype(dtype)
    v = (jax.random.normal(jax.random.fold_in(key, 2),
                           (B * kv, S, hd)) * 0.5).astype(dtype)
    got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 bq=128, bk=128)
    want = attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_flash_attention_bidirectional():
    q = jax.random.normal(jax.random.key(0), (2, 256, 64))
    k = jax.random.normal(jax.random.key(1), (2, 256, 64))
    v = jax.random.normal(jax.random.key(2), (2, 256, 64))
    got = flash_attention_pallas(q, k, v, causal=False, bq=128, bk=128)
    want = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


# ---------------------------------------------------------------------------
# mamba scan


@pytest.mark.parametrize("S,D,N", [(256, 64, 8), (512, 128, 16), (256, 32, 4)])
@pytest.mark.slow
def test_mamba_scan_sweep(S, D, N):
    B = 2
    key = jax.random.key(S * D)
    u = jax.random.normal(key, (B, S, D))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (B, S, D)) - 1.0)
    bm = jax.random.normal(jax.random.fold_in(key, 2), (B, S, N))
    cm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, N))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 4), (D, N)))
    got = mamba_scan_pallas(u, dt, bm, cm, A, bd=32, bs=128)
    want = mamba_scan_ref(u, dt, bm, cm, A)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


def test_mamba_scan_state_carries_across_blocks():
    """With decay ~1 and constant input, h accumulates linearly across the
    whole sequence — catching any scratch reset between seq blocks."""
    B, S, D, N = 1, 512, 32, 4
    u = jnp.ones((B, S, D))
    dt = jnp.full((B, S, D), 1e-3)
    bm = jnp.ones((B, S, N))
    cm = jnp.ones((B, S, N))
    A = jnp.full((D, N), -1e-6)
    y = mamba_scan_pallas(u, dt, bm, cm, A, bd=32, bs=128)
    # y[t] ~ N * (t+1) * dt — strictly increasing across block boundaries
    yt = np.asarray(y[0, :, 0])
    assert (np.diff(yt) > 0).all()
    np.testing.assert_allclose(yt[-1] / yt[127], S / 128.0, rtol=1e-2)


# ---------------------------------------------------------------------------
# interpret-mode selection


_GM = gf.generator_matrix(8, 2)
_RS_BP = gf.bitplane_matrix(_GM)
_BYTES = np.arange(8 * 4096, dtype=np.uint32).astype(np.uint8)


@pytest.mark.parametrize("kernel,ref,args", [
    (lambda d: rs_encode_pallas(d, _RS_BP),
     lambda d: rs_encode_np(d, _GM), (_BYTES.reshape(8, -1),)),
    (checksum_pallas,
     lambda d, n: checksum_ref(jnp.asarray(d), jnp.asarray(n)),
     (_BYTES[:16 * 128].reshape(16, -1), np.arange(16, dtype=np.int32) * 8)),
], ids=["rs_encode", "checksum"])
def test_kernel_interprets_on_cpu_and_compiles_for_tpu(kernel, ref, args):
    """The CPU runs the Pallas interpreter; a program lowered for a TPU
    holds the compiled kernel — interpret mode is never picked there."""
    fn = jax.jit(kernel)
    assert "tpu_custom_call" not in fn.lower(*args).as_text()
    np.testing.assert_array_equal(np.asarray(fn(*args)),
                                  np.asarray(ref(*args)))
    tpu = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in tpu


# ---------------------------------------------------------------------------
# ops-level wrappers (model layout)


def test_flash_attention_ops_model_layout():
    from repro.kernels.flash_attention import ops as fops
    B, S, KV, G, hd = 2, 256, 2, 2, 64
    key = jax.random.key(0)
    q = jax.random.normal(key, (B, S, KV, G, hd)) * 0.5
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd)) * 0.5
    got = fops.flash_attention(q, k, v, causal=True, bq=128, bk=128)
    ref = fops.flash_attention(q, k, v, causal=True, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    # and against the model's own XLA attention path
    from repro.models import layers as L
    qg = q.reshape(B, S, KV, G, hd)
    want = L._attn_online(qg, k, v, jnp.arange(S), jnp.arange(S), 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=2e-4)


def test_checksum_ops_jit_selectable():
    from repro.kernels.checksum import ops as cops
    rng = np.random.default_rng(5)
    data = jnp.asarray(rng.integers(0, 256, (4, 128), dtype=np.uint8))
    length = jnp.asarray([128, 0, 65, 7], jnp.int32)
    a = cops.checksum(data, length, use_pallas=True)
    b = cops.checksum(data, length, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
