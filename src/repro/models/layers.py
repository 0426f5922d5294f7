"""Model-zoo primitives: norms, rotary embeddings, attention (full /
chunked-flash XLA / decode), GLU FFNs, scatter-based MoE, RG-LRU and RWKV-6
mixers, in pure JAX (params are nested dicts; apply fns are functional).

Conventions
-----------
* activations: (B, S, d) in ``cfg.dtype`` (bf16 by default)
* attention heads: q (B, S, H, hd); k/v (B, S, K, hd); G = H // K
* softmax / recurrences / norms accumulate in fp32
* every ``init_*`` returns a params dict; every ``apply`` is pure
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig

Params = Any


class NullAnnotator:
    """No-op activation-sharding annotator (single-device tests)."""
    dp_size: int = 1
    moe_groups: int = 1

    def constrain(self, x, kind: str):
        return x


NULL_ANN = NullAnnotator()


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _normal(key, shape, std, dtype):
    return (std * jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dim: Optional[int] = None) -> Params:
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), _pdtype(cfg))}
    if cfg.norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), _pdtype(cfg))
    return p


def apply_norm(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        var = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE + Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------

def rope_angles(positions: jax.Array, head_dim: int, theta: float,
                mrope_sections: Optional[tuple[int, ...]] = None) -> jax.Array:
    """positions: (B, S) or (3, B, S) for M-RoPE -> angles (B, S, head_dim//2)."""
    half = head_dim // 2
    inv_freq = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    inv_freq = jnp.asarray(inv_freq)
    if mrope_sections is None:
        pos = positions.astype(jnp.float32)
        return pos[..., None] * inv_freq[None, None, :]
    # M-RoPE: frequency slots are split into (t, h, w) sections, each taking
    # its position id from the corresponding plane of ``positions`` (3,B,S).
    assert positions.ndim == 3 and positions.shape[0] == len(mrope_sections)
    parts = []
    start = 0
    for sec_idx, sec in enumerate(mrope_sections):
        pos = positions[sec_idx].astype(jnp.float32)          # (B, S)
        parts.append(pos[..., None] * inv_freq[None, None, start:start + sec])
        start += sec
    assert start == half, "mrope sections must sum to head_dim//2"
    return jnp.concatenate(parts, axis=-1)


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """x: (B, S, N, hd), angles: (B, S, hd//2) — half-split (llama) convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig) -> Params:
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    std = 0.02
    p = {
        "wq": _normal(ks[0], (d, H, hd), std, _pdtype(cfg)),
        "wk": _normal(ks[1], (d, K, hd), std, _pdtype(cfg)),
        "wv": _normal(ks[2], (d, K, hd), std, _pdtype(cfg)),
        "wo": _normal(ks[3], (H, hd, d), std / math.sqrt(2 * cfg.num_layers), _pdtype(cfg)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), _pdtype(cfg))
        p["bk"] = jnp.zeros((K, hd), _pdtype(cfg))
        p["bv"] = jnp.zeros((K, hd), _pdtype(cfg))
    return p


def _qkv(p: Params, x: jax.Array, cfg: ModelConfig):
    dt = x.dtype
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return q, k, v


def _out_proj(p: Params, o: jax.Array, dt) -> jax.Array:
    return jnp.einsum("bsnh,nhd->bsd", o, p["wo"].astype(dt))


def _mask_bias(q_pos, k_pos, causal: bool, window: int) -> jax.Array:
    """(..., Q, KV) additive fp32 bias: 0 allowed / -inf masked."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    allow = (kp <= qp) if causal else jnp.full(jnp.broadcast_shapes(qp.shape, kp.shape), True)
    if window > 0:
        allow = allow & (qp - kp < window)
    return jnp.where(allow, 0.0, -1e30).astype(jnp.float32)


def _softcap(logits: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0:
        return cap * jnp.tanh(logits / cap)
    return logits


def full_attention(q, k, v, *, causal: bool, window: int = 0,
                   q_offset: int = 0, softcap: float = 0.0) -> jax.Array:
    """Reference full attention; q/k/v: (B, S, H, hd) (KV already repeated
    to H heads — see ``attention_sequence``)."""
    B, Sq, H, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqnh,bsnh->bnqs", q, k).astype(jnp.float32) * scale
    logits = _softcap(logits, softcap)
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(k.shape[1])
    logits = logits + _mask_bias(q_pos, k_pos, causal, window)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnqs,bsnh->bqnh", w, v)


def flash_attention_xla(q, k, v, *, causal: bool, window: int = 0,
                        chunk_q: int = 512, chunk_kv: int = 1024,
                        softcap: float = 0.0) -> jax.Array:
    """Memory-bounded chunked attention with running softmax (pure XLA).

    q/k/v: (B, S, H, hd), heads TP-shardable.  Outer scan over q chunks
    (each remat'd so the bwd never keeps softmax probabilities for more
    than one block pair), inner scan over kv chunks; fp32 accumulators.
    Working set per step is (Cq x Ckv) — never materializes (S x S).
    """
    B, S, H, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    Cq = min(chunk_q, S)
    Ckv = min(chunk_kv, k.shape[1])
    nq = S // Cq
    nkv = k.shape[1] // Ckv
    assert S % Cq == 0 and k.shape[1] % Ckv == 0, "seq not divisible by chunks"

    qg = q.reshape(B, nq, Cq, H, hd)
    kg = k.reshape(B, nkv, Ckv, H, hd)
    vg = v.reshape(B, nkv, Ckv, H, hd)

    def q_block(qi, qc, kg, vg):  # qc: (B, Cq, H, hd)
        q_pos = qi * Cq + jnp.arange(Cq)

        def kv_block(carry, inputs):
            m, l, acc = carry
            kj, kc, vc = inputs
            k_pos = kj * Ckv + jnp.arange(Ckv)
            s = jnp.einsum("bqnh,bsnh->bnqs", qc, kc).astype(jnp.float32) * scale
            s = _softcap(s, softcap)
            s = s + _mask_bias(q_pos, k_pos, causal, window)
            # the running max only keeps exp in range: the output does not
            # depend on it, so no gradient flows through it.  Differentiating
            # max() ties gradients to equality with the recomputed scores,
            # which a fused TPU recomputation can miss (0/0: NaN grads).
            m_new = jax.lax.stop_gradient(jnp.maximum(m, s.max(-1)))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + p.sum(-1)
            pv = jnp.einsum("bnqs,bsnh->bnqh", p.astype(qc.dtype), vc).astype(jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, Cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, Cq), jnp.float32)
        a0 = jnp.zeros((B, H, Cq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0),
            (jnp.arange(nkv), kg.swapaxes(0, 1), vg.swapaxes(0, 1)))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return o.astype(q.dtype)  # (B, H, Cq, hd)

    q_block = jax.checkpoint(q_block, static_argnums=())

    def scan_q(_, inputs):
        qi, qc = inputs
        return None, q_block(qi, qc, kg, vg)

    _, oq = jax.lax.scan(scan_q, None, (jnp.arange(nq), qg.swapaxes(0, 1)))
    # oq: (nq, B, H, Cq, hd) -> (B, S, H, hd)
    o = oq.transpose(1, 0, 3, 2, 4).reshape(B, S, H, hd)
    return o


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     softcap: float = 0.0) -> jax.Array:
    """Single-token decode: q (B, 1, H, hd), caches (B, Smax, K, hd).

    ``pos`` (B,) is the index of the *current* token (its K/V already
    written); entries with k_pos > pos are masked.
    """
    B, _, H, hd = q.shape
    Smax, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, K, G, hd)
    assert k_cache.dtype != jnp.int8, "dequantize int8 KV before decode_attention"
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache).astype(jnp.float32) * scale
    s = _softcap(s, softcap)
    k_pos = jnp.arange(Smax)[None, :]
    allow = k_pos <= pos[:, None]
    if window > 0:
        allow = allow & (pos[:, None] - k_pos < window)
    s = s + jnp.where(allow, 0.0, -1e30)[:, None, None, :]
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgs,bskh->bkgh", w, v_cache)
    return o.reshape(B, 1, H, hd)


def attention_sequence(p: Params, x: jax.Array, cfg: ModelConfig, *,
                       positions: jax.Array, causal: bool = True,
                       window: int = 0, kv_override=None,
                       return_kv: bool = False, ann=NULL_ANN):
    """Attention over a full sequence (train / prefill).

    GQA KV is repeated up to H heads before the score einsum so the head
    dim shards cleanly over the TP axis even when num_kv_heads < tp (the
    repeat is a gather; FLOPs are identical to the grouped einsum).
    ``return_kv`` returns the *un-repeated* K/V for the KV cache.

    kv_override: (k, v, kv_angles) for cross-attention (whisper decoder;
    no RoPE applied on either side).
    """
    dt = x.dtype
    if kv_override is None:
        q, k, v = _qkv(p, x, cfg)
        angles = rope_angles(positions, cfg.resolved_head_dim, cfg.rope_theta,
                             tuple(cfg.mrope_sections) if cfg.mrope_sections else None)
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)
    else:
        q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(dt))
        if "bq" in p:
            q = q + p["bq"].astype(dt)
        k, v, _ = kv_override

    kv_out = (k, v)
    G = cfg.num_heads // k.shape[2]
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    q = ann.constrain(q, "heads")
    k = ann.constrain(k, "heads")
    v = ann.constrain(v, "heads")

    S = x.shape[1]
    use_flash = cfg.attn_impl in ("xla_chunked", "pallas") and S > cfg.attn_chunk_q \
        and S % cfg.attn_chunk_q == 0 and k.shape[1] % cfg.attn_chunk_kv == 0
    if use_flash:
        o = flash_attention_xla(q, k, v, causal=causal, window=window,
                                chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv,
                                softcap=cfg.attn_logit_softcap)
    else:
        o = full_attention(q, k, v, causal=causal, window=window,
                           softcap=cfg.attn_logit_softcap)
    out = _out_proj(p, o, dt)
    if return_kv:
        return out, kv_out
    return out


def attention_decode_step(p: Params, x: jax.Array, cfg: ModelConfig, *,
                          pos: jax.Array, k_cache, v_cache,
                          window: int = 0, cross_kv=None):
    """One-token decode. x: (B, 1, d); pos: (B,) current position.

    Returns (out, (k_cache, v_cache)) with the new K/V written at ``pos``
    (ring-buffer write when ``window`` > 0 and the cache holds only the
    window).
    """
    dt = x.dtype
    if cross_kv is not None:
        k, v = cross_kv
        q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(dt))
        if "bq" in p:
            q = q + p["bq"].astype(dt)
        B = x.shape[0]
        q_ang = rope_angles(pos[:, None], cfg.resolved_head_dim, cfg.rope_theta, None)
        q = apply_rope(q, q_ang)
        Smax = k.shape[1]
        s = jnp.einsum("bkgh,bskh->bkgs",
                       q.reshape(B, cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, -1),
                       k).astype(jnp.float32) / math.sqrt(cfg.resolved_head_dim)
        w = jax.nn.softmax(s, -1).astype(dt)
        o = jnp.einsum("bkgs,bskh->bkgh", w, v).reshape(B, 1, cfg.num_heads, -1)
        return _out_proj(p, o, dt), None

    q, k, v = _qkv(p, x, cfg)
    if cfg.mrope_sections:
        mpos = jnp.broadcast_to(pos[None, :, None], (3,) + pos.shape + (1,))
        angles = rope_angles(mpos, cfg.resolved_head_dim, cfg.rope_theta,
                             tuple(cfg.mrope_sections))
    else:
        angles = rope_angles(pos[:, None], cfg.resolved_head_dim, cfg.rope_theta, None)
    q = apply_rope(q, angles)
    k = apply_rope(k, angles)

    Smax = k_cache.shape[1]
    write_idx = (pos % Smax) if window > 0 else pos
    bidx = jnp.arange(x.shape[0])
    int8_kv = k_cache.dtype == jnp.int8
    if int8_kv:
        # static symmetric int8 KV quantization (beyond-paper decode lever:
        # halves cache HBM traffic; see EXPERIMENTS.md §Roofline decode note)
        qs = cfg.kv_quant_scale
        k_w = jnp.clip(jnp.round(k[:, 0].astype(jnp.float32) / qs), -127, 127)
        v_w = jnp.clip(jnp.round(v[:, 0].astype(jnp.float32) / qs), -127, 127)
        k_cache = k_cache.at[bidx, write_idx].set(k_w.astype(jnp.int8))
        v_cache = v_cache.at[bidx, write_idx].set(v_w.astype(jnp.int8))
        k_full = (k_cache.astype(dt) * jnp.asarray(qs, dt))
        v_full = (v_cache.astype(dt) * jnp.asarray(qs, dt))
    else:
        k_cache = k_cache.at[bidx, write_idx].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[bidx, write_idx].set(v[:, 0].astype(v_cache.dtype))
        k_full, v_full = k_cache.astype(dt), v_cache.astype(dt)
    if window > 0:
        # ring buffer: every live entry is within the window -> no pos mask
        o = decode_attention(q, k_full, v_full,
                             jnp.full_like(pos, Smax), window=0,
                             softcap=cfg.attn_logit_softcap)
    else:
        o = decode_attention(q, k_full, v_full, pos,
                             softcap=cfg.attn_logit_softcap)
    return _out_proj(p, o, dt), (k_cache, v_cache)


# ---------------------------------------------------------------------------
# FFN (dense)
# ---------------------------------------------------------------------------

def init_ffn(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    std = 0.02
    gated = cfg.activation in ("swiglu", "geglu")
    p = {"w_up": _normal(ks[0], (d, f), std, _pdtype(cfg)),
         "w_down": _normal(ks[1], (f, d), std / math.sqrt(2 * cfg.num_layers), _pdtype(cfg))}
    if gated:
        p["w_gate"] = _normal(ks[2], (d, f), std, _pdtype(cfg))
    return p


def _act(name: str, g: jax.Array) -> jax.Array:
    if name in ("swiglu", "silu"):
        return jax.nn.silu(g)
    if name in ("geglu", "gelu"):
        return jax.nn.gelu(g)
    if name == "relu_sq":
        r = jax.nn.relu(g)
        return r * r
    raise ValueError(name)


def apply_ffn(p: Params, x: jax.Array, cfg: ModelConfig, ann=NULL_ANN) -> jax.Array:
    dt = x.dtype
    up = ann.constrain(x @ p["w_up"].astype(dt), "wide")
    if "w_gate" in p:
        gate = _act(cfg.activation, ann.constrain(x @ p["w_gate"].astype(dt), "wide"))
        h = gate * up
    else:
        h = _act(cfg.activation, up)
    return h @ p["w_down"].astype(dt)


# ---------------------------------------------------------------------------
# MoE (scatter-based top-k dispatch, GShard-style capacity)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig) -> Params:
    assert cfg.moe is not None
    d, E, f = cfg.d_model, cfg.moe.num_experts, cfg.moe.d_ff_expert
    ks = jax.random.split(key, 4)
    std = 0.02
    gated = cfg.activation in ("swiglu", "geglu")
    p = {
        "router": _normal(ks[0], (d, E), std, _pdtype(cfg)),
        "w_up": _normal(ks[1], (E, d, f), std, _pdtype(cfg)),
        "w_down": _normal(ks[2], (E, f, d), std / math.sqrt(2 * cfg.num_layers), _pdtype(cfg)),
    }
    if gated:
        p["w_gate"] = _normal(ks[3], (E, d, f), std, _pdtype(cfg))
    return p


def apply_moe(p: Params, x: jax.Array, cfg: ModelConfig, ann=NULL_ANN):
    """Top-k MoE, GShard-style grouped dispatch with scatter (no one-hot
    dispatch einsum).

    Tokens are split into G groups (G = the data-parallel degree so routing
    stays group-local and the dispatch scatter is fully local per shard);
    each group routes its tokens into a capacity-bounded (E, C, d) buffer,
    expert FFNs run as a batched einsum over E (GSPMD inserts the expert
    all-to-all from the sharding annotations), results gather back with the
    top-k gate-weighted combine.  Overflowed tokens drop (GShard
    semantics).  Returns (y, aux_loss).
    """
    assert cfg.moe is not None
    mo = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, k = mo.num_experts, mo.top_k
    dt = x.dtype
    G = max(1, min(ann.moe_groups, T))
    while T % G != 0:      # G always divides T in production (B % dp == 0)
        G -= 1
    Tg = T // G
    xt = x.reshape(G, Tg, d)

    logits = (xt @ p["router"].astype(dt)).astype(jnp.float32)   # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)                     # (G, Tg, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    C = int(max(4, math.ceil(k * Tg / E * mo.capacity_factor)))
    C = min(C, k * Tg)

    e_flat = idx.reshape(G, Tg * k)                              # (G, Tg*k)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)          # (G, Tg*k, E)
    pos_all = jnp.cumsum(onehot, axis=1) - 1                     # position within expert
    pos = jnp.take_along_axis(pos_all, e_flat[..., None], axis=2)[..., 0]
    valid = pos < C
    pos_c = jnp.where(valid, pos, C)                             # overflow -> slot C

    # per-group scatter into (E, C+1, d); slot C is the trash slot.
    # one scatter per top-k rank keeps updates at (G, Tg, d) — never
    # materializes the (G, Tg*k, d) repeat.
    def scatter_group(xg, eg, pg):
        buf = jnp.zeros((E, C + 1, d), dt)
        for j in range(k):
            buf = buf.at[eg[:, j], pg[:, j]].add(xg)
        return buf

    e_tk = e_flat.reshape(G, Tg, k)
    p_tk = pos_c.reshape(G, Tg, k)
    buf = jax.vmap(scatter_group)(xt, e_tk, p_tk)                # (G, E, C+1, d)
    buf = ann.constrain(buf[:, :, :C], "moe_buf")                # (G, E, C, d)

    up = jnp.einsum("gecd,edf->gecf", buf, p["w_up"].astype(dt))
    if "w_gate" in p:
        g = _act(cfg.activation,
                 jnp.einsum("gecd,edf->gecf", buf, p["w_gate"].astype(dt)))
        h = g * up
    else:
        h = _act(cfg.activation, up)
    h = ann.constrain(h, "moe_hidden")
    out = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(dt))  # (G, E, C, d)
    out = ann.constrain(out, "moe_buf")

    out = jnp.concatenate([out, jnp.zeros((G, E, 1, d), dt)], axis=2)

    def gather_group(og, eg, pg, wg):
        y = jnp.zeros((Tg, d), dt)
        for j in range(k):
            y = y + og[eg[:, j], pg[:, j]] * wg[:, j][:, None]
        return y

    w_tk = (gate_vals * valid.reshape(G, Tg, k)).astype(dt)
    y = jax.vmap(gather_group)(out, e_tk, p_tk, w_tk)            # (G, Tg, d)
    y = y.reshape(B, S, d)

    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    f_e = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    p_e = probs.mean(axis=(0, 1))
    aux = E * jnp.sum(f_e * p_e) * mo.aux_loss_weight
    return y, aux


# ---------------------------------------------------------------------------
# RG-LRU (recurrentgemma) recurrent block
# ---------------------------------------------------------------------------

def init_rglru_block(key, cfg: ModelConfig) -> Params:
    assert cfg.recurrent is not None
    d = cfg.d_model
    lru = cfg.recurrent.lru_width or d
    cw = cfg.recurrent.conv1d_width
    ks = jax.random.split(key, 7)
    std = 0.02
    # a_param init so that a = sigmoid(lambda)^c in [0.9, 0.999]
    a_init = jnp.log(jnp.expm1(-(1.0 / 8.0) * jnp.log(
        jnp.linspace(0.9, 0.999, lru, dtype=jnp.float32))) + 0.0)
    return {
        "w_x": _normal(ks[0], (d, lru), std, _pdtype(cfg)),
        "w_gate": _normal(ks[1], (d, lru), std, _pdtype(cfg)),
        "w_out": _normal(ks[2], (lru, d), std / math.sqrt(2 * cfg.num_layers), _pdtype(cfg)),
        "conv_w": _normal(ks[3], (cw, lru), std, _pdtype(cfg)),
        "conv_b": jnp.zeros((lru,), _pdtype(cfg)),
        # diagonal input/recurrence gates (block-diagonal in the paper;
        # diagonal here — noted simplification, same state dynamics)
        "gate_i_w": _normal(ks[4], (lru,), std, _pdtype(cfg)),
        "gate_i_b": jnp.zeros((lru,), _pdtype(cfg)),
        "gate_r_w": _normal(ks[5], (lru,), std, _pdtype(cfg)),
        "gate_r_b": jnp.zeros((lru,), _pdtype(cfg)),
        "a_param": a_init.astype(_pdtype(cfg)),
    }


def _rglru_gates(p, u):
    """u: (..., lru) branch input -> (a, gated_in) fp32."""
    uf = u.astype(jnp.float32)
    gi = jax.nn.sigmoid(uf * p["gate_i_w"].astype(jnp.float32) + p["gate_i_b"].astype(jnp.float32))
    gr = jax.nn.sigmoid(uf * p["gate_r_w"].astype(jnp.float32) + p["gate_r_b"].astype(jnp.float32))
    log_a = -8.0 * gr * jax.nn.softplus(p["a_param"].astype(jnp.float32))
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    return a, mult * gi * uf


def rglru_sequence(p: Params, x: jax.Array, cfg: ModelConfig, *,
                   h0: Optional[jax.Array] = None, conv_state=None,
                   chunk: int = 256, ann=NULL_ANN):
    """RG-LRU block over a sequence. x: (B, S, d) -> (y, (h_last, conv_tail)).

    h_t = a_t * h_{t-1} + sqrt(1-a_t^2) * (i_t * u_t), associative-scanned
    per chunk (remat between chunks keeps bwd memory linear in n_chunks).
    """
    B, S, d = x.shape
    dt = x.dtype
    u = ann.constrain(x @ p["w_x"].astype(dt), "wide")       # (B, S, lru)
    gate = ann.constrain(jax.nn.gelu(x @ p["w_gate"].astype(dt)), "wide")
    lru = u.shape[-1]
    cw = cfg.recurrent.conv1d_width

    if conv_state is None:
        conv_state = jnp.zeros((B, cw - 1, lru), dt)
    u_pad = jnp.concatenate([conv_state, u], axis=1)
    conv_w = p["conv_w"].astype(dt)
    uc = sum(u_pad[:, i:i + S] * conv_w[i] for i in range(cw)) + p["conv_b"].astype(dt)
    new_conv_state = u_pad[:, -(cw - 1):] if cw > 1 else conv_state

    a, b = _rglru_gates(p, uc)                  # fp32 (B, S, lru)
    if h0 is None:
        h0 = jnp.zeros((B, lru), jnp.float32)

    Ck = min(chunk, S)
    nchunks = max(1, S // Ck)
    assert S % Ck == 0 or nchunks == 1, "seq not divisible by rglru chunk"
    if S % Ck != 0:
        Ck, nchunks = S, 1
    a_c = a.reshape(B, nchunks, Ck, lru).swapaxes(0, 1)
    b_c = b.reshape(B, nchunks, Ck, lru).swapaxes(0, 1)

    def chunk_step(h, ab):
        ac, bc = ab                              # (B, Ck, lru) fp32

        def combine(l, r):
            al, bl = l
            ar, br = r
            return al * ar, ar * bl + br

        aa, bb = jax.lax.associative_scan(combine, (ac, bc), axis=1)
        h_seq = aa * h[:, None, :] + bb
        return h_seq[:, -1, :], h_seq

    chunk_step = jax.checkpoint(chunk_step)
    h_last, hs = jax.lax.scan(chunk_step, h0, (a_c, b_c))
    h_seq = hs.swapaxes(0, 1).reshape(B, S, lru).astype(dt)
    y = (h_seq * gate) @ p["w_out"].astype(dt)
    return y, (h_last, new_conv_state)


def rglru_decode_step(p: Params, x: jax.Array, cfg: ModelConfig, *,
                      h: jax.Array, conv_state: jax.Array):
    """One-token RG-LRU step. x: (B, 1, d); h: (B, lru) fp32; conv_state (B, cw-1, lru)."""
    B = x.shape[0]
    dt = x.dtype
    u = (x[:, 0] @ p["w_x"].astype(dt))          # (B, lru)
    gate = jax.nn.gelu(x[:, 0] @ p["w_gate"].astype(dt))
    cw = cfg.recurrent.conv1d_width
    conv_w = p["conv_w"].astype(dt)
    hist = jnp.concatenate([conv_state, u[:, None]], axis=1)     # (B, cw, lru)
    uc = jnp.einsum("bcl,cl->bl", hist, conv_w) + p["conv_b"].astype(dt)
    new_conv_state = hist[:, 1:]
    a, b = _rglru_gates(p, uc)
    h_new = a * h + b
    y = ((h_new.astype(dt) * gate) @ p["w_out"].astype(dt))[:, None]
    return y, (h_new, new_conv_state)


# ---------------------------------------------------------------------------
# RWKV-6 (Finch) token mix + channel mix
# ---------------------------------------------------------------------------

def init_rwkv_block(key, cfg: ModelConfig) -> Params:
    assert cfg.rwkv is not None
    d = cfg.d_model
    hs = cfg.rwkv.head_size
    H = d // hs
    dl, gl = cfg.rwkv.decay_lora, cfg.rwkv.gate_lora
    ks = jax.random.split(key, 12)
    std = 0.02
    pd = _pdtype(cfg)
    return {
        "mu_r": jnp.full((d,), 0.5, pd), "mu_k": jnp.full((d,), 0.5, pd),
        "mu_v": jnp.full((d,), 0.5, pd), "mu_w": jnp.full((d,), 0.5, pd),
        "mu_g": jnp.full((d,), 0.5, pd),
        "w_r": _normal(ks[0], (d, d), std, pd),
        "w_k": _normal(ks[1], (d, d), std, pd),
        "w_v": _normal(ks[2], (d, d), std, pd),
        "w_g": _normal(ks[3], (d, d), std, pd),
        "w_o": _normal(ks[4], (d, d), std / math.sqrt(2 * cfg.num_layers), pd),
        # data-dependent decay LoRA (the Finch feature)
        "w0": jnp.full((d,), -6.0, pd),
        "wA": _normal(ks[5], (d, dl), std, pd),
        "wB": _normal(ks[6], (dl, d), std, pd),
        "u_bonus": _normal(ks[7], (H, hs), std, pd),
        "ln_scale": jnp.ones((d,), pd), "ln_bias": jnp.zeros((d,), pd),
        # channel mix
        "cm_mu_k": jnp.full((d,), 0.5, pd), "cm_mu_r": jnp.full((d,), 0.5, pd),
        "cm_wk": _normal(ks[8], (d, cfg.d_ff), std, pd),
        "cm_wv": _normal(ks[9], (cfg.d_ff, d), std / math.sqrt(2 * cfg.num_layers), pd),
        "cm_wr": _normal(ks[10], (d, d), std, pd),
    }


def _rwkv_wkv_scan(r, k, v, w, u, s0, chunk: int = 128):
    """WKV-6 recurrence.  r/k/v/w: (B, S, H, hs) fp32; u: (H, hs); s0: (B, H, hs, hs).

    y_t[j] = sum_i r_t[i] * (S_t[i,j] + u[i] k_t[i] v_t[j])
    S_{t+1}[i,j] = w_t[i] * S_t[i,j] + k_t[i] v_t[j]
    Chunked outer scan with remat'd inner scan (bwd memory ~ n_chunks states).
    """
    B, S, H, hs = r.shape
    Ck = min(chunk, S)
    if S % Ck != 0:
        Ck = S
    nc = S // Ck

    def to_chunks(x):
        return x.reshape(B, nc, Ck, H, hs).transpose(1, 2, 0, 3, 4)  # (nc, Ck, B, H, hs)

    rc, kc, vc, wc = map(to_chunks, (r, k, v, w))

    def inner(s, rkvw):
        rt, kt, vt, wt = rkvw                    # (B, H, hs)
        kv = kt[..., :, None] * vt[..., None, :]  # (B, H, hs, hs)
        y = jnp.einsum("bhi,bhij->bhj", rt, s + u[None, :, :, None] * kv)
        s_new = wt[..., :, None] * s + kv
        return s_new, y

    def outer(s, ch):
        rc_, kc_, vc_, wc_ = ch

        def run(s):
            return jax.lax.scan(inner, s, (rc_, kc_, vc_, wc_))

        s_new, ys = jax.checkpoint(run)(s)
        return s_new, ys

    s_last, ys = jax.lax.scan(outer, s0, (rc, kc, vc, wc))
    # ys: (nc, Ck, B, H, hs) -> (B, S, H, hs)
    y = ys.reshape(nc * Ck, B, H, hs).transpose(1, 0, 2, 3)
    return y, s_last


def rwkv_time_mix(p: Params, x: jax.Array, cfg: ModelConfig, *,
                  x_prev: Optional[jax.Array] = None,
                  state: Optional[jax.Array] = None):
    """RWKV-6 time mix over a sequence. x: (B, S, d).

    Returns (y, (last_x, last_state)).
    """
    B, S, d = x.shape
    dt = x.dtype
    hs = cfg.rwkv.head_size
    H = d // hs
    if x_prev is None:
        x_prev = jnp.zeros((B, d), dt)
    xs = jnp.concatenate([x_prev[:, None], x[:, :-1]], axis=1)   # shifted
    dx = xs - x

    def mix(mu):
        return x + dx * mu.astype(dt)

    xr, xk, xv, xw, xg = (mix(p[m]) for m in ("mu_r", "mu_k", "mu_v", "mu_w", "mu_g"))
    r = (xr @ p["w_r"].astype(dt)).reshape(B, S, H, hs).astype(jnp.float32)
    k = (xk @ p["w_k"].astype(dt)).reshape(B, S, H, hs).astype(jnp.float32)
    v = (xv @ p["w_v"].astype(dt)).reshape(B, S, H, hs).astype(jnp.float32)
    g = jax.nn.silu(xg @ p["w_g"].astype(dt))
    dd = jnp.tanh(xw.astype(jnp.float32) @ p["wA"].astype(jnp.float32)) @ p["wB"].astype(jnp.float32)
    w = jnp.exp(-jnp.exp(p["w0"].astype(jnp.float32) + dd)).reshape(B, S, H, hs)
    if state is None:
        state = jnp.zeros((B, H, hs, hs), jnp.float32)
    y, s_last = _rwkv_wkv_scan(r, k, v, w, p["u_bonus"].astype(jnp.float32), state)
    # per-head groupnorm
    yf = y.reshape(B, S, H, hs)
    mu = yf.mean(-1, keepdims=True)
    var = ((yf - mu) ** 2).mean(-1, keepdims=True)
    yn = ((yf - mu) * jax.lax.rsqrt(var + 64e-5)).reshape(B, S, d)
    yn = yn * p["ln_scale"].astype(jnp.float32) + p["ln_bias"].astype(jnp.float32)
    out = (yn.astype(dt) * g) @ p["w_o"].astype(dt)
    return out, (x[:, -1], s_last)


def rwkv_channel_mix(p: Params, x: jax.Array, cfg: ModelConfig, *,
                     x_prev: Optional[jax.Array] = None):
    B, S, d = x.shape
    dt = x.dtype
    if x_prev is None:
        x_prev = jnp.zeros((B, d), dt)
    xs = jnp.concatenate([x_prev[:, None], x[:, :-1]], axis=1)
    dx = xs - x
    xk = x + dx * p["cm_mu_k"].astype(dt)
    xr = x + dx * p["cm_mu_r"].astype(dt)
    kk = jax.nn.relu(xk @ p["cm_wk"].astype(dt))
    vv = (kk * kk) @ p["cm_wv"].astype(dt)
    rr = jax.nn.sigmoid(xr @ p["cm_wr"].astype(dt))
    return rr * vv, x[:, -1]


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def init_embeddings(key, cfg: ModelConfig) -> Params:
    V, d = cfg.padded_vocab, cfg.d_model
    ks = jax.random.split(key, 2)
    p = {"embed": _normal(ks[0], (V, d), 0.02, _pdtype(cfg))}
    if not cfg.tie_embeddings:
        p["unembed"] = _normal(ks[1], (d, V), 0.02, _pdtype(cfg))
    return p


def embed_tokens(p: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    return p["embed"].astype(_dtype(cfg))[tokens]


def logits_from_hidden(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    dt = x.dtype
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, p["embed"].astype(dt))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["unembed"].astype(dt))
    if cfg.padded_vocab != cfg.vocab_size:
        pad_bias = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab_size, 0.0, -1e30)
        logits = logits + pad_bias.astype(dt)
    return logits


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token CE in fp32. logits (B, S, V); labels (B, S) int32.

    The gold logit is extracted with a masked reduction along the vocab dim
    rather than ``take_along_axis`` — a gather along the TP-sharded vocab
    axis would force GSPMD to all-gather the full fp32 logits per device.
    The masked reduce partitions cleanly (vocab-sharded reduce + tiny
    all-reduce).
    """
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(lf.max(axis=-1, keepdims=True))
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1))
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], lf, 0.0), axis=-1)
    return jnp.mean(lse - gold)
