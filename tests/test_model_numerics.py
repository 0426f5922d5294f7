"""Model-layer numerics: chunked-flash XLA path vs full attention; rglru /
wkv jnp paths vs their kernel oracles; MoE grouping invariance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig, MoEConfig, RecurrentConfig, RWKVConfig
from repro.models import layers as L


def test_flash_xla_matches_full_attention():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, H, hd = 2, 256, 4, 64
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    full = L.full_attention(q, k, v, causal=True)
    fl = L.flash_attention_xla(q, k, v, causal=True, chunk_q=64, chunk_kv=64)
    np.testing.assert_allclose(np.asarray(fl), np.asarray(full),
                               atol=2e-5, rtol=2e-5)


def test_flash_xla_window_matches_full():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, H, hd = 1, 256, 2, 32
    q, k, v = (jax.random.normal(kk, (B, S, H, hd)) for kk in ks)
    full = L.full_attention(q, k, v, causal=True, window=48)
    fl = L.flash_attention_xla(q, k, v, causal=True, window=48,
                               chunk_q=64, chunk_kv=64)
    np.testing.assert_allclose(np.asarray(fl), np.asarray(full), atol=2e-5)


@pytest.mark.parametrize("window", [0, 48])
def test_flash_xla_grads_match_full_attention(window):
    """The chunked path's running max carries no gradient: d/dq, d/dk and
    d/dv still equal full attention's, with a causal mask and a window."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    B, S, H, hd = 1, 256, 2, 32
    q, k, v = (jax.random.normal(kk, (B, S, H, hd)) for kk in ks[:3])
    cot = jax.random.normal(ks[3], (B, S, H, hd))

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) * cot)

    full = jax.grad(loss(lambda q, k, v: L.full_attention(
        q, k, v, causal=True, window=window)), argnums=(0, 1, 2))(q, k, v)
    fl = jax.grad(loss(lambda q, k, v: L.flash_attention_xla(
        q, k, v, causal=True, window=window, chunk_q=64, chunk_kv=64)),
        argnums=(0, 1, 2))(q, k, v)
    for name, g_fl, g_full in zip("qkv", fl, full):
        assert np.isfinite(np.asarray(g_fl)).all(), name
        np.testing.assert_allclose(np.asarray(g_fl), np.asarray(g_full),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


def test_rglru_sequence_matches_kernel_ref():
    """The model's rglru_sequence recurrence == the kernel oracle recurrence
    given identical gates."""
    from repro.kernels.rglru.ref import rglru_ref
    cfg = ModelConfig(name="t", family="hybrid", num_layers=3, d_model=32,
                      num_heads=2, num_kv_heads=1, d_ff=64, vocab_size=128,
                      recurrent=RecurrentConfig(lru_width=32),
                      dtype="float32")
    p = L.init_rglru_block(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 32)) * 0.5
    y, (h_last, conv) = L.rglru_sequence(p, x, cfg, chunk=16)
    # recompute gates exactly as the layer does, then run the oracle scan
    dt = x.dtype
    u = x @ p["w_x"].astype(dt)
    cw = cfg.recurrent.conv1d_width
    u_pad = jnp.concatenate([jnp.zeros((2, cw - 1, 32), dt), u], axis=1)
    conv_w = p["conv_w"].astype(dt)
    uc = sum(u_pad[:, i:i + 64] * conv_w[i] for i in range(cw)) + p["conv_b"].astype(dt)
    a, b = L._rglru_gates(p, uc)
    h_ref = rglru_ref(a, b, jnp.zeros((2, 32)))
    gate = jax.nn.gelu(x @ p["w_gate"].astype(dt))
    y_ref = (h_ref.astype(dt) * gate) @ p["w_out"].astype(dt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(h_ref[:, -1]),
                               atol=1e-4)


def test_rglru_decode_steps_match_sequence():
    cfg = ModelConfig(name="t", family="hybrid", num_layers=3, d_model=16,
                      num_heads=2, num_kv_heads=1, d_ff=32, vocab_size=128,
                      recurrent=RecurrentConfig(lru_width=16), dtype="float32")
    p = L.init_rglru_block(jax.random.PRNGKey(2), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 12, 16)) * 0.5
    y_seq, (h_seq, conv_seq) = L.rglru_sequence(p, x, cfg, chunk=4)
    h = jnp.zeros((1, 16), jnp.float32)
    conv = jnp.zeros((1, cfg.recurrent.conv1d_width - 1, 16), jnp.float32)
    ys = []
    for t in range(12):
        y_t, (h, conv) = L.rglru_decode_step(p, x[:, t:t + 1], cfg,
                                             h=h, conv_state=conv)
        ys.append(y_t)
    y_dec = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_seq), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_seq), atol=1e-4)


def test_rwkv_time_mix_matches_kernel_ref():
    from repro.kernels.rwkv6.ref import wkv6_ref
    cfg = ModelConfig(name="t", family="ssm", num_layers=2, d_model=32,
                      num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=128,
                      rwkv=RWKVConfig(head_size=16, decay_lora=8),
                      dtype="float32", norm_type="layernorm")
    p = L.init_rwkv_block(jax.random.PRNGKey(4), cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 20, 32)) * 0.5
    y, (x_last, s_last) = L.rwkv_time_mix(p, x, cfg)
    assert y.shape == x.shape
    assert np.all(np.isfinite(np.asarray(y)))
    np.testing.assert_allclose(np.asarray(x_last), np.asarray(x[:, -1]))


def test_moe_group_count_changes_only_capacity_drops():
    """With ample capacity, the grouped dispatch output is independent of
    the number of groups (the dp-local grouping is semantics-preserving)."""
    cfg = ModelConfig(name="t", family="moe", num_layers=2, d_model=32,
                      num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=128,
                      moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=64,
                                    capacity_factor=8.0),
                      dtype="float32")
    p = L.init_moe(jax.random.PRNGKey(6), cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 8, 32)) * 0.5

    class Ann(L.NullAnnotator):
        def __init__(self, g):
            self.moe_groups = g

    y1, aux1 = L.apply_moe(p, x, cfg, ann=Ann(1))
    y4, aux4 = L.apply_moe(p, x, cfg, ann=Ann(4))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y4), atol=1e-5)


def test_moe_capacity_drops_tokens():
    cfg = ModelConfig(name="t", family="moe", num_layers=2, d_model=16,
                      num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=128,
                      moe=MoEConfig(num_experts=2, top_k=2, d_ff_expert=32,
                                    capacity_factor=0.1),
                      dtype="float32")
    p = L.init_moe(jax.random.PRNGKey(8), cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 16))
    y, _ = L.apply_moe(p, x, cfg)
    # with capacity_factor 0.1 most tokens drop -> many zero rows
    zero_rows = np.mean(np.all(np.asarray(y) == 0, axis=-1))
    assert zero_rows > 0.3


def test_mrope_sections_positional_structure():
    angles = L.rope_angles(jnp.stack([jnp.arange(8)[None] * 1,
                                      jnp.arange(8)[None] * 2,
                                      jnp.arange(8)[None] * 3]),
                           head_dim=16, theta=100.0,
                           mrope_sections=(3, 3, 2))
    assert angles.shape == (1, 8, 8)
    # section boundaries use different position streams
    assert not np.allclose(np.asarray(angles[0, :, 0]),
                           np.asarray(angles[0, :, 3]))


def test_cross_entropy_matches_naive():
    rng = jax.random.PRNGKey(10)
    logits = jax.random.normal(rng, (2, 8, 50))
    labels = jax.random.randint(rng, (2, 8), 0, 50)
    ours = L.cross_entropy(logits, labels)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    naive = jnp.mean(lse - gold)
    np.testing.assert_allclose(float(ours), float(naive), rtol=1e-6)
