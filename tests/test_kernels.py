"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import example, given, settings, strategies as st

from repro.kernels.ckpt_delta.ops import delta_decode, delta_encode
from repro.kernels.ckpt_delta.ref import GROUP, decode_ref, encode_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru.ops import rglru_scan
from repro.kernels.rglru.ref import rglru_ref
from repro.kernels.rwkv6.ops import wkv6
from repro.kernels.rwkv6.ref import wkv6_ref


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("B,S,H,K,hd", [
    (1, 128, 4, 4, 64),     # MHA
    (2, 256, 4, 2, 64),     # GQA
    (1, 256, 8, 1, 128),    # MQA, MXU-width head
    (1, 512, 2, 2, 256),    # RG-style 256 head dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(B, S, H, K, hd, dtype, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, K, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, K, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_kv=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_softcap():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    out = flash_attention(q, k, v, causal=True, softcap=30.0,
                          block_q=64, block_kv=64, interpret=True)
    ref = attention_ref(q, k, v, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


@pytest.mark.parametrize("B,S,D,ct,bd", [
    (1, 128, 128, 64, 128),
    (2, 512, 256, 128, 128),
    (1, 256, 512, 256, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_sweep(B, S, D, ct, bd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    a = (jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, D))) * 0.2 + 0.79).astype(dtype)
    b = (jax.random.normal(ks[1], (B, S, D)) * 0.1).astype(dtype)
    h0 = (jax.random.normal(ks[2], (B, D)) * 0.5).astype(jnp.float32)
    out = rglru_scan(a, b, h0, chunk_t=ct, block_d=bd, interpret=True)
    ref = rglru_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **_tol(dtype))


@pytest.mark.parametrize("B,S,H,hs,ct", [
    (1, 128, 2, 16, 64),
    (2, 256, 2, 32, 128),
    (1, 128, 4, 64, 32),
])
def test_wkv6_sweep(B, S, H, hs, ct):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    r, k, v = (jax.random.normal(kk, (B, S, H, hs)) * 0.5 for kk in ks[:3])
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, hs))) * 0.3 + 0.65
    u = jax.random.normal(ks[4], (H, hs)) * 0.3
    s0 = jnp.zeros((B, H, hs, hs))
    y, s = wkv6(r, k, v, w, u, s0, chunk_t=ct, interpret=True)
    yr, sr = wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=2e-4)


def test_wkv6_state_carry_matches_two_chunks():
    """Running S=256 in one call == two sequential 128-calls via s carry."""
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    B, S, H, hs = 1, 256, 2, 16
    r, k, v = (jax.random.normal(kk, (B, S, H, hs)) * 0.5 for kk in ks[:3])
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, hs))) * 0.3 + 0.65
    u = jax.random.normal(ks[4], (H, hs)) * 0.3
    s0 = jnp.zeros((B, H, hs, hs))
    y_all, s_all = wkv6(r, k, v, w, u, s0, chunk_t=64, interpret=True)
    y1, s1 = wkv6(r[:, :128], k[:, :128], v[:, :128], w[:, :128], u, s0,
                  chunk_t=64, interpret=True)
    y2, s2 = wkv6(r[:, 128:], k[:, 128:], v[:, 128:], w[:, 128:], u, s1,
                  chunk_t=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y_all[:, 128:]), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_all), np.asarray(s2), atol=1e-4)


@pytest.mark.parametrize("n", [1024, 4096, 5000, 100_000])
def test_ckpt_delta_kernel_vs_ref(n):
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    new = jax.random.normal(ks[0], (n,))
    base = new + jax.random.normal(ks[1], (n,)) * 0.01
    q, s = delta_encode(new, base, interpret=True)
    qr, sr = encode_ref(np.asarray(new) - np.asarray(base))
    np.testing.assert_allclose(np.asarray(s), sr, rtol=1e-6)
    assert np.mean(np.asarray(q) == qr) > 0.999   # round ties may differ
    d = delta_decode(q, s, interpret=True)[:n]
    dr = decode_ref(qr, sr)[:n]
    np.testing.assert_allclose(np.asarray(d), dr, atol=1e-6)


@pytest.mark.parametrize("n", [1024, 4096, 5000])
def test_ckpt_lossless_kernel_bit_exact_vs_ref(n):
    """The fused lossless sub+XOR-residual kernel must match its host
    oracle bit for bit, and decode must reproduce the original f32 bit
    patterns exactly (this is what keeps lossless restore bit-exact when
    the encode runs on-device)."""
    from repro.kernels.ckpt_delta.ops import lossless_decode, lossless_encode
    from repro.kernels.ckpt_delta.ref import (lossless_decode_ref,
                                              lossless_encode_ref)

    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    new = jax.random.normal(ks[0], (n,))
    base = new + jax.random.normal(ks[1], (n,)) * 1e-3
    d, r = lossless_encode(new, base, interpret=True)
    dr, rr = lossless_encode_ref(np.asarray(new), np.asarray(base))
    assert np.array_equal(np.asarray(d)[:n], dr)
    assert np.array_equal(np.asarray(r)[:n], rr)
    # kernel decode: original bit patterns back, exactly
    out = np.asarray(lossless_decode(base, d, r, interpret=True))[:n]
    assert np.array_equal(out.view(np.uint32),
                          np.asarray(new).view(np.uint32))
    # host oracle decode agrees bitwise too
    out_ref = lossless_decode_ref(np.asarray(base), dr, rr)
    assert np.array_equal(out_ref.view(np.uint32),
                          np.asarray(new).view(np.uint32))
    # the u32 residual's bytes equal the legacy per-byte u8 XOR, so blobs
    # written by either path stay mutually restorable
    pred = np.asarray(base) + dr
    legacy = np.frombuffer(np.asarray(new).tobytes(), np.uint8) \
        ^ np.frombuffer(pred.tobytes(), np.uint8)
    assert legacy.tobytes() == rr.tobytes()


def _check_ckpt_delta_roundtrip_error_bound(n, scale, seed):
    """Property: |delta - decode(encode(delta))| <= group_scale/2 plus the
    two float32 roundings of the codec, elementwise.  Encode rounds
    ``delta/scale`` (|.| <= 127, so off by at most 127 * 2**-24 before
    ``round``) and decode rounds ``q*scale`` (|q| <= 127, off by at most
    127 * scale * 2**-24): together 254 * scale * 2**-24 on top of the
    half step.  The error is measured in float64 so the check adds no
    rounding of its own."""
    rng = np.random.default_rng(seed)
    delta = (rng.standard_normal(n) * scale).astype(np.float32)
    q, s = encode_ref(delta)
    rec = decode_ref(q, s)[:n]
    group_scales = np.repeat(s, GROUP)[:n].astype(np.float64)
    err = np.abs(delta.astype(np.float64) - rec.astype(np.float64))
    assert np.all(err <= group_scales * (0.5 + 254 * 2.0 ** -24))


@settings(max_examples=25, deadline=None)
@given(n=st.integers(8, 5000), scale=st.floats(1e-4, 1e3),
       seed=st.integers(0, 2**16))
@example(n=4323, scale=1.015625, seed=64954)   # exceeds a bare scale/2
def test_ckpt_delta_roundtrip_error_bound(n, scale, seed):
    _check_ckpt_delta_roundtrip_error_bound(n, scale, seed)
