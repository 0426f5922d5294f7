"""Model zoo: family stacks (dense / moe / hybrid / ssm / vlm / audio),
train/prefill/decode step factories, and ShapeDtypeStruct input specs for
the dry-run.

All stacks scan over layers (``lax.scan`` with stacked params) so HLO size
is depth-independent — required to compile 48–64 layer models against a
512-way mesh.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, ShapeConfig, OptimizerConfig
from repro.models import layers as L
from repro.optim import Optimizer, clip_by_global_norm

Params = Any

VLM_VISION_TOKENS = 1024   # stub frontend: fixed number of precomputed patch embeddings


# ---------------------------------------------------------------------------
# Remat
# ---------------------------------------------------------------------------

def _remat(fn, cfg: ModelConfig):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "minimal":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


def _stack_init(init_fn, key, n: int):
    """Initialize n layers with stacked (leading-axis n) params."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def _index_tree(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, rng: jax.Array) -> Params:
    keys = jax.random.split(rng, 8)
    p: dict = {"emb": L.init_embeddings(keys[0], cfg),
               "final_norm": L.init_norm(cfg)}

    if cfg.family in ("dense", "vlm"):
        def one(k):
            ks = jax.random.split(k, 2)
            return {"ln1": L.init_norm(cfg), "attn": L.init_attention(ks[0], cfg),
                    "ln2": L.init_norm(cfg), "ffn": L.init_ffn(ks[1], cfg)}
        p["layers"] = _stack_init(one, keys[1], cfg.num_layers)

    elif cfg.family == "moe":
        def one(k):
            ks = jax.random.split(k, 2)
            return {"ln1": L.init_norm(cfg), "attn": L.init_attention(ks[0], cfg),
                    "ln2": L.init_norm(cfg), "moe": L.init_moe(ks[1], cfg)}
        p["layers"] = _stack_init(one, keys[1], cfg.num_layers)

    elif cfg.family == "ssm":
        def one(k):
            return {"ln1": L.init_norm(cfg), "ln2": L.init_norm(cfg),
                    "mix": L.init_rwkv_block(k, cfg)}
        p["layers"] = _stack_init(one, keys[1], cfg.num_layers)

    elif cfg.family == "hybrid":
        pat = tuple(cfg.recurrent.block_pattern)
        period = len(pat)
        n_rec_per_group = sum(1 for b in pat if b == "recurrent")
        n_groups = cfg.num_layers // period
        n_tail = cfg.num_layers - n_groups * period
        assert all(b == "recurrent" for b in pat[:n_tail]), "tail must be recurrent-only"

        def rec_one(k):
            ks = jax.random.split(k, 2)
            return {"ln": L.init_norm(cfg), "mix": L.init_rglru_block(ks[0], cfg),
                    "ffn_ln": L.init_norm(cfg), "ffn": L.init_ffn(ks[1], cfg)}

        def attn_one(k):
            ks = jax.random.split(k, 2)
            return {"ln": L.init_norm(cfg), "attn": L.init_attention(ks[0], cfg),
                    "ffn_ln": L.init_norm(cfg), "ffn": L.init_ffn(ks[1], cfg)}

        def group_one(k):
            ks = jax.random.split(k, 2)
            return {"rec": _stack_init(rec_one, ks[0], n_rec_per_group),
                    "attn": attn_one(ks[1])}

        p["groups"] = _stack_init(group_one, keys[1], n_groups)
        if n_tail:
            p["tail"] = _stack_init(rec_one, keys[2], n_tail)

    elif cfg.family == "audio":
        def enc_one(k):
            ks = jax.random.split(k, 2)
            return {"ln1": L.init_norm(cfg), "attn": L.init_attention(ks[0], cfg),
                    "ln2": L.init_norm(cfg), "ffn": L.init_ffn(ks[1], cfg)}

        def dec_one(k):
            ks = jax.random.split(k, 3)
            return {"ln1": L.init_norm(cfg), "self_attn": L.init_attention(ks[0], cfg),
                    "ln2": L.init_norm(cfg), "cross_attn": L.init_attention(ks[1], cfg),
                    "ln3": L.init_norm(cfg), "ffn": L.init_ffn(ks[2], cfg)}

        p["enc_layers"] = _stack_init(enc_one, keys[1], cfg.num_layers)
        p["enc_norm"] = L.init_norm(cfg)
        p["dec_layers"] = _stack_init(dec_one, keys[2], cfg.num_decoder_layers)
    else:
        raise ValueError(cfg.family)
    return p


# ---------------------------------------------------------------------------
# Sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def _run_uniform_stack(params, cfg: ModelConfig, x, positions, *,
                       collect_kv: bool, causal: bool = True, window: int = 0,
                       ann=L.NULL_ANN):
    """dense/moe/vlm/ssm stacks (uniform per-layer structure)."""

    def body(carry, lp):
        x, aux = carry
        x = ann.constrain(x, "hidden")
        ys = None
        if cfg.family == "ssm":
            h = L.apply_norm(lp["ln1"], x, cfg)
            y, (tm_x, tm_s) = L.rwkv_time_mix(lp["mix"], h, cfg)
            x = x + y
            h = L.apply_norm(lp["ln2"], x, cfg)
            y, cm_x = L.rwkv_channel_mix(lp["mix"], h, cfg)
            x = x + y
            if collect_kv:
                ys = {"tm_x": tm_x, "tm_s": tm_s, "cm_x": cm_x}
        else:
            h = L.apply_norm(lp["ln1"], x, cfg)
            if collect_kv:
                a, (k, v) = L.attention_sequence(
                    lp["attn"], h, cfg, positions=positions, causal=causal,
                    window=window, return_kv=True, ann=ann)
                ys = {"k": k, "v": v}
            else:
                a = L.attention_sequence(lp["attn"], h, cfg, positions=positions,
                                         causal=causal, window=window, ann=ann)
            x = ann.constrain(x + a, "hidden")
            h = L.apply_norm(lp["ln2"], x, cfg)
            if cfg.family == "moe":
                y, aux_l = L.apply_moe(lp["moe"], h, cfg, ann=ann)
                aux = aux + aux_l
            else:
                y = L.apply_ffn(lp["ffn"], h, cfg, ann=ann)
            x = x + y
        return (ann.constrain(x, "hidden"), aux), ys

    body = _remat(body, cfg)
    (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                    params["layers"])
    return x, aux, caches


def _hybrid_sublayer(lp, x, cfg, positions, kind: str, collect: bool,
                     ann=L.NULL_ANN):
    h = L.apply_norm(lp["ln"], x, cfg)
    st = None
    if kind == "recurrent":
        y, (h_last, conv_tail) = L.rglru_sequence(lp["mix"], h, cfg, ann=ann)
        if collect:
            st = {"h": h_last, "conv": conv_tail}
    else:
        W = cfg.recurrent.window_size
        if collect:
            y, (k, v) = L.attention_sequence(lp["attn"], h, cfg, positions=positions,
                                             causal=True, window=W, return_kv=True,
                                             ann=ann)
            S = k.shape[1]
            if S < W:
                pad = [(0, 0), (0, W - S), (0, 0), (0, 0)]
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            else:
                assert S % W == 0, "hybrid prefill needs seq % window == 0"
                k, v = k[:, -W:], v[:, -W:]
            st = {"k": k, "v": v}
        else:
            y = L.attention_sequence(lp["attn"], h, cfg, positions=positions,
                                     causal=True, window=W, ann=ann)
    x = ann.constrain(x + y, "hidden")
    h = L.apply_norm(lp["ffn_ln"], x, cfg)
    x = ann.constrain(x + L.apply_ffn(lp["ffn"], h, cfg, ann=ann), "hidden")
    return x, st


def _run_hybrid_stack(params, cfg: ModelConfig, x, positions, *, collect_kv: bool,
                      ann=L.NULL_ANN):
    pat = tuple(cfg.recurrent.block_pattern)
    n_rec = sum(1 for b in pat if b == "recurrent")

    def group_body(carry, gp):
        x, = carry
        x = ann.constrain(x, "hidden")
        recs = []
        for i in range(n_rec):
            x, st = _hybrid_sublayer(_index_tree(gp["rec"], i), x, cfg, positions,
                                     "recurrent", collect_kv, ann)
            recs.append(st)
        x, attn_st = _hybrid_sublayer(gp["attn"], x, cfg, positions,
                                      "attention", collect_kv, ann)
        ys = None
        if collect_kv:
            ys = {"rec": jax.tree_util.tree_map(lambda *a: jnp.stack(a), *recs),
                  "attn": attn_st}
        return (x,), ys

    group_body = _remat(group_body, cfg)
    (x,), group_caches = jax.lax.scan(group_body, (x,), params["groups"])

    tail_caches = []
    if "tail" in params:
        n_tail = jax.tree_util.tree_leaves(params["tail"])[0].shape[0]
        for i in range(n_tail):
            x, st = _hybrid_sublayer(_index_tree(params["tail"], i), x, cfg,
                                     positions, "recurrent", collect_kv, ann)
            tail_caches.append(st)
    caches = None
    if collect_kv:
        caches = {"groups": group_caches}
        if tail_caches:
            caches["tail"] = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *tail_caches)
    return x, caches


def _run_audio_stack(params, cfg: ModelConfig, frames, dec_x, dec_positions, *,
                     collect_kv: bool, ann=L.NULL_ANN):
    enc_positions = jnp.broadcast_to(jnp.arange(frames.shape[1])[None],
                                     frames.shape[:2])

    def enc_body(x, lp):
        x = ann.constrain(x, "hidden")
        h = L.apply_norm(lp["ln1"], x, cfg)
        x = x + L.attention_sequence(lp["attn"], h, cfg, positions=enc_positions,
                                     causal=False, ann=ann)
        h = L.apply_norm(lp["ln2"], x, cfg)
        x = x + L.apply_ffn(lp["ffn"], h, cfg, ann=ann)
        return ann.constrain(x, "hidden"), None

    enc_body = _remat(enc_body, cfg)
    enc_out, _ = jax.lax.scan(enc_body, frames, params["enc_layers"])
    enc_out = L.apply_norm(params["enc_norm"], enc_out, cfg)

    def dec_body(x, lp):
        dt = x.dtype
        x = ann.constrain(x, "hidden")
        h = L.apply_norm(lp["ln1"], x, cfg)
        ys = None
        if collect_kv:
            a, (k, v) = L.attention_sequence(lp["self_attn"], h, cfg,
                                             positions=dec_positions, causal=True,
                                             return_kv=True, ann=ann)
        else:
            a = L.attention_sequence(lp["self_attn"], h, cfg,
                                     positions=dec_positions, causal=True, ann=ann)
        x = x + a
        h = L.apply_norm(lp["ln2"], x, cfg)
        ck = jnp.einsum("bsd,dnh->bsnh", enc_out, lp["cross_attn"]["wk"].astype(dt))
        cv = jnp.einsum("bsd,dnh->bsnh", enc_out, lp["cross_attn"]["wv"].astype(dt))
        x = x + L.attention_sequence(lp["cross_attn"], h, cfg,
                                     positions=dec_positions, causal=False,
                                     kv_override=(ck, cv, None), ann=ann)
        h = L.apply_norm(lp["ln3"], x, cfg)
        x = ann.constrain(x + L.apply_ffn(lp["ffn"], h, cfg, ann=ann), "hidden")
        if collect_kv:
            ys = {"k": k, "v": v, "ck": ck, "cv": cv}
        return x, ys

    dec_body = _remat(dec_body, cfg)
    x, caches = jax.lax.scan(dec_body, dec_x, params["dec_layers"])
    return x, caches


def forward_logits(params, cfg: ModelConfig, batch: dict, *,
                   collect_kv: bool = False, last_token_only: bool = False,
                   ann=L.NULL_ANN):
    """Sequence forward for train/prefill. Returns (logits, aux, caches).

    ``last_token_only`` (prefill) computes logits for the final position
    only — avoids materializing the (B, S, V) logits for 32k prefills.
    """
    if cfg.family == "audio":
        frames = batch["frames"].astype(L._dtype(cfg))
        dec_tokens = batch["dec_tokens"]
        dec_x = L.embed_tokens(params["emb"], dec_tokens, cfg)
        B, Sd = dec_tokens.shape
        dec_pos = jnp.broadcast_to(jnp.arange(Sd)[None], (B, Sd))
        x, caches = _run_audio_stack(params, cfg, frames, dec_x, dec_pos,
                                     collect_kv=collect_kv, ann=ann)
        aux = jnp.zeros((), jnp.float32)
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = L.embed_tokens(params["emb"], tokens, cfg)
        if cfg.family == "vlm" and "vision_embeds" in batch:
            ve = batch["vision_embeds"].astype(x.dtype)
            nv = ve.shape[1]
            x = jnp.concatenate([x[:, :nv] + ve, x[:, nv:]], axis=1)
        x = ann.constrain(x, "hidden")
        if cfg.mrope_sections and "positions" in batch:
            positions = batch["positions"]
        else:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            if cfg.mrope_sections:
                positions = jnp.broadcast_to(positions[None], (3, B, S))
        if cfg.family == "hybrid":
            pos2d = positions if positions.ndim == 2 else positions[0]
            x, caches = _run_hybrid_stack(params, cfg, x, pos2d,
                                          collect_kv=collect_kv, ann=ann)
            aux = jnp.zeros((), jnp.float32)
        else:
            x, aux, caches = _run_uniform_stack(params, cfg, x, positions,
                                                collect_kv=collect_kv, ann=ann)
    if last_token_only:
        x = x[:, -1:]
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = ann.constrain(L.logits_from_hidden(params["emb"], x, cfg), "logits")
    return logits, aux, caches


# ---------------------------------------------------------------------------
# Decode forward
# ---------------------------------------------------------------------------

def forward_decode(params, cfg: ModelConfig, caches, tokens, pos,
                   ann=L.NULL_ANN):
    """One decode step. tokens (B, 1) int32; pos (B,) int32.

    Returns (logits (B, vocab_pad), new_caches).
    """
    x = L.embed_tokens(params["emb"], tokens, cfg)

    if cfg.family in ("dense", "vlm", "moe"):
        def body(carry, xs):
            x, aux = carry
            lp, kc, vc = xs
            h = L.apply_norm(lp["ln1"], x, cfg)
            a, (kc, vc) = L.attention_decode_step(lp["attn"], h, cfg, pos=pos,
                                                  k_cache=kc, v_cache=vc)
            x = x + a
            h = L.apply_norm(lp["ln2"], x, cfg)
            if cfg.family == "moe":
                y, aux_l = L.apply_moe(lp["moe"], h, cfg, ann=ann)
                aux = aux + aux_l
            else:
                y = L.apply_ffn(lp["ffn"], h, cfg)
            x = x + y
            return (x, aux), {"k": kc, "v": vc}

        (x, _), new_caches = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (params["layers"], caches["k"], caches["v"]))

    elif cfg.family == "ssm":
        def body(x, xs):
            lp, st = xs
            h = L.apply_norm(lp["ln1"], x, cfg)
            y, (tm_x, tm_s) = L.rwkv_time_mix(lp["mix"], h, cfg,
                                              x_prev=st["tm_x"], state=st["tm_s"])
            x = x + y
            h = L.apply_norm(lp["ln2"], x, cfg)
            y, cm_x = L.rwkv_channel_mix(lp["mix"], h, cfg, x_prev=st["cm_x"])
            x = x + y
            return x, {"tm_x": tm_x, "tm_s": tm_s, "cm_x": cm_x}

        x, new_caches = jax.lax.scan(body, x, (params["layers"], caches))

    elif cfg.family == "hybrid":
        n_rec = sum(1 for b in cfg.recurrent.block_pattern if b == "recurrent")
        W = cfg.recurrent.window_size

        def sub_rec(lp, x, st):
            h = L.apply_norm(lp["ln"], x, cfg)
            y, (hh, conv) = L.rglru_decode_step(lp["mix"], h, cfg,
                                                h=st["h"], conv_state=st["conv"])
            x = x + y
            h = L.apply_norm(lp["ffn_ln"], x, cfg)
            x = x + L.apply_ffn(lp["ffn"], h, cfg)
            return x, {"h": hh, "conv": conv}

        def sub_attn(lp, x, st):
            h = L.apply_norm(lp["ln"], x, cfg)
            a, (kc, vc) = L.attention_decode_step(lp["attn"], h, cfg, pos=pos,
                                                  k_cache=st["k"], v_cache=st["v"],
                                                  window=W)
            x = x + a
            h = L.apply_norm(lp["ffn_ln"], x, cfg)
            x = x + L.apply_ffn(lp["ffn"], h, cfg)
            return x, {"k": kc, "v": vc}

        def group_body(x, xs):
            gp, st = xs
            new_rec = []
            for i in range(n_rec):
                x, s = sub_rec(_index_tree(gp["rec"], i), x, _index_tree(st["rec"], i))
                new_rec.append(s)
            x, s_attn = sub_attn(gp["attn"], x, st["attn"])
            return x, {"rec": jax.tree_util.tree_map(lambda *a: jnp.stack(a), *new_rec),
                       "attn": s_attn}

        x, new_group = jax.lax.scan(group_body, x,
                                    (params["groups"], caches["groups"]))
        new_caches = {"groups": new_group}
        if "tail" in params:
            n_tail = jax.tree_util.tree_leaves(params["tail"])[0].shape[0]
            tails = []
            for i in range(n_tail):
                x, s = sub_rec(_index_tree(params["tail"], i), x,
                               _index_tree(caches["tail"], i))
                tails.append(s)
            new_caches["tail"] = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *tails)

    elif cfg.family == "audio":
        def body(x, xs):
            lp, st = xs
            h = L.apply_norm(lp["ln1"], x, cfg)
            a, (kc, vc) = L.attention_decode_step(lp["self_attn"], h, cfg, pos=pos,
                                                  k_cache=st["k"], v_cache=st["v"])
            x = x + a
            h = L.apply_norm(lp["ln2"], x, cfg)
            a, _ = L.attention_decode_step(lp["cross_attn"], h, cfg, pos=pos,
                                           k_cache=None, v_cache=None,
                                           cross_kv=(st["ck"], st["cv"]))
            x = x + a
            h = L.apply_norm(lp["ln3"], x, cfg)
            x = x + L.apply_ffn(lp["ffn"], h, cfg)
            return x, {"k": kc, "v": vc, "ck": st["ck"], "cv": st["cv"]}

        x, new_caches = jax.lax.scan(body, x, (params["dec_layers"], caches))
    else:
        raise ValueError(cfg.family)

    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.logits_from_hidden(params["emb"], x, cfg)[:, 0]
    return logits, new_caches


# ---------------------------------------------------------------------------
# Step factories
# ---------------------------------------------------------------------------

def make_loss_fn(cfg: ModelConfig, ann=L.NULL_ANN):
    def loss_fn(params, batch):
        logits, aux, _ = forward_logits(params, cfg, batch, ann=ann)
        labels = batch["labels"]
        ce = L.cross_entropy(logits, labels)
        return ce + aux, {"ce": ce, "aux": aux}
    return loss_fn


def make_train_step(cfg: ModelConfig, optimizer: Optimizer,
                    opt_cfg: OptimizerConfig, accum: int = 1, ann=L.NULL_ANN,
                    accum_dtype: str = "float32"):
    model_loss = make_loss_fn(cfg, ann=ann)

    def loss_fn(params, batch):
        # names the forward ops, and the backward ops that inherit it, in
        # the device trace; XLA computes the same program
        with jax.named_scope("model"):
            return model_loss(params, batch)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    adt = jnp.dtype(accum_dtype)

    def train_step(state, batch):
        params = state["params"]
        if accum <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def micro(carry, mb):
                gsum, lsum = carry
                (l, _), g = grad_fn(params, mb)
                gsum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(adt), gsum, g)
                return (gsum, lsum + l), None

            z = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, adt), params)
            bsz = (batch.get("tokens", batch.get("frames"))).shape[0]

            def split_micro(x):
                """Split the global-batch dim into (accum, B/accum) —
                handles leading-batch leaves and (3, B, S) position ids."""
                if x.shape[0] == bsz:
                    return x.reshape((accum, bsz // accum) + x.shape[1:])
                if x.ndim >= 2 and x.shape[1] == bsz:
                    y = x.reshape(x.shape[:1] + (accum, bsz // accum) + x.shape[2:])
                    return jnp.moveaxis(y, 1, 0)
                raise ValueError(f"cannot microbatch leaf of shape {x.shape}")

            mbs = jax.tree_util.tree_map(split_micro, batch)
            (grads, loss), _ = jax.lax.scan(micro, (z, jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree_util.tree_map(lambda g: (g / accum).astype(jnp.float32), grads)
            loss = loss / accum
            metrics = {"ce": loss, "aux": jnp.zeros((), jnp.float32)}

        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, opt_cfg.grad_clip)
            new_params, new_opt = optimizer.update(grads, state["opt"],
                                                   params, state["step"])
        new_state = {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
        out_metrics = {"loss": loss, "grad_norm": gnorm, **metrics}
        return new_state, out_metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, ann=L.NULL_ANN):
    def prefill(params, inputs):
        logits, _, caches = forward_logits(params, cfg, inputs, collect_kv=True,
                                           last_token_only=True, ann=ann)
        next_token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_token, caches
    return prefill


def make_decode_step(cfg: ModelConfig, ann=L.NULL_ANN):
    def decode(params, caches, inputs):
        logits, new_caches = forward_decode(params, cfg, caches,
                                            inputs["tokens"], inputs["pos"],
                                            ann=ann)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token[:, None], new_caches
    return decode


# ---------------------------------------------------------------------------
# Input / cache specs (ShapeDtypeStruct, no allocation) — dry-run substrate
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this (arch, shape)."""
    B, S = shape.global_batch, shape.seq_len
    dt = cfg.dtype
    if shape.mode == "train":
        if cfg.family == "audio":
            Sd = S // cfg.dec_ratio
            return {"frames": _sds((B, S, cfg.d_model), dt),
                    "dec_tokens": _sds((B, Sd), "int32"),
                    "labels": _sds((B, Sd), "int32")}
        spec = {"tokens": _sds((B, S), "int32"), "labels": _sds((B, S), "int32")}
        if cfg.family == "vlm":
            spec["vision_embeds"] = _sds((B, VLM_VISION_TOKENS, cfg.d_model), dt)
            spec["positions"] = _sds((3, B, S), "int32")
        return spec
    if shape.mode == "prefill":
        if cfg.family == "audio":
            Sd = S // cfg.dec_ratio
            return {"frames": _sds((B, S, cfg.d_model), dt),
                    "dec_tokens": _sds((B, Sd), "int32")}
        spec = {"tokens": _sds((B, S), "int32")}
        if cfg.family == "vlm":
            spec["vision_embeds"] = _sds((B, VLM_VISION_TOKENS, cfg.d_model), dt)
            spec["positions"] = _sds((3, B, S), "int32")
        return spec
    # decode
    return {"tokens": _sds((B, 1), "int32"), "pos": _sds((B,), "int32")}


def cache_specs(cfg: ModelConfig, shape: ShapeConfig) -> Any:
    """KV-cache / state ShapeDtypeStructs for decode shapes."""
    assert shape.mode == "decode"
    B, S = shape.global_batch, shape.seq_len
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    kvdt = cfg.kv_cache_dtype
    if cfg.family in ("dense", "vlm", "moe"):
        LN = cfg.num_layers
        return {"k": _sds((LN, B, S, K, hd), kvdt),
                "v": _sds((LN, B, S, K, hd), kvdt)}
    if cfg.family == "ssm":
        H = cfg.d_model // cfg.rwkv.head_size
        hs = cfg.rwkv.head_size
        Lx = cfg.num_layers
        return {"tm_x": _sds((Lx, B, cfg.d_model), cfg.dtype),
                "tm_s": _sds((Lx, B, H, hs, hs), "float32"),
                "cm_x": _sds((Lx, B, cfg.d_model), cfg.dtype)}
    if cfg.family == "hybrid":
        pat = tuple(cfg.recurrent.block_pattern)
        n_rec = sum(1 for b in pat if b == "recurrent")
        G = cfg.num_layers // len(pat)
        n_tail = cfg.num_layers - G * len(pat)
        lru = cfg.recurrent.lru_width or cfg.d_model
        cw = cfg.recurrent.conv1d_width
        W = cfg.recurrent.window_size
        rec = {"h": _sds((G, n_rec, B, lru), "float32"),
               "conv": _sds((G, n_rec, B, cw - 1, lru), cfg.dtype)}
        attn = {"k": _sds((G, B, W, K, hd), kvdt),
                "v": _sds((G, B, W, K, hd), kvdt)}
        caches = {"groups": {"rec": rec, "attn": attn}}
        if n_tail:
            caches["tail"] = {"h": _sds((n_tail, B, lru), "float32"),
                              "conv": _sds((n_tail, B, cw - 1, lru), cfg.dtype)}
        return caches
    if cfg.family == "audio":
        Ld = cfg.num_decoder_layers
        Se = S // cfg.dec_ratio
        return {"k": _sds((Ld, B, S, K, hd), kvdt),
                "v": _sds((Ld, B, S, K, hd), kvdt),
                "ck": _sds((Ld, B, Se, K, hd), kvdt),
                "cv": _sds((Ld, B, Se, K, hd), kvdt)}
    raise ValueError(cfg.family)


def init_state(cfg: ModelConfig, optimizer: Optimizer, rng: jax.Array) -> dict:
    """TrainState {params, opt, step} with freshly initialized params."""
    params = init_params(cfg, rng)
    return {"params": params, "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def state_specs(cfg: ModelConfig, optimizer: Optimizer) -> dict:
    """TrainState ShapeDtypeStructs via eval_shape (no allocation)."""
    return jax.eval_shape(partial(init_state, cfg, optimizer),
                          jax.random.PRNGKey(0))


def build_model(cfg: ModelConfig):
    """Convenience bundle for examples/tests."""
    return {
        "init": partial(init_params, cfg),
        "loss_fn": make_loss_fn(cfg),
        "prefill": make_prefill_step(cfg),
        "decode": make_decode_step(cfg),
        "input_specs": partial(input_specs, cfg),
        "cache_specs": partial(cache_specs, cfg),
    }
