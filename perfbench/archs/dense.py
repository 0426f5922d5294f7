"""The dense decoder: RMSNorm, RoPE, grouped-query attention and a SwiGLU
feed-forward in every layer, the layers stacked on a leading axis, an
untied output head unless the configuration ties it.

Everything of the benchmark that depends on the model's shape, each a
function of the configuration's dict:

  * ``model_config``: the program's ``ModelConfig`` for it;
  * ``init_params`` and ``sequence_loss``: the plain float32 reference,
    which imports nothing of the program;
  * ``param_shapes`` and ``train_flops_per_token``: what the arithmetic
    counts;
  * ``tiny``: the CPU tests' size, widths cut and structure kept.
"""
from __future__ import annotations

import math

import numpy as np

QUERY_BLOCK = 256


def model_config(cfg: dict, **override):
    from repro.config import ModelConfig
    job = cfg["job"]
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        activation="swiglu", norm_type="rmsnorm",
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg.get("tie_word_embeddings", False),
        dtype=job["compute_dtype"],
        param_dtype=override.get("param_dtype", job["param_dtype"]))


def tiny(cfg: dict) -> dict:
    cfg = dict(cfg, hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=2, vocab_size=500)
    cfg["job"] = dict(cfg["job"], seq_len=32, batch=4)
    return cfg


# ---------------------------------------------------------------------------
# shapes and operations
# ---------------------------------------------------------------------------

def padded_vocab(cfg: dict, multiple: int = 256) -> int:
    return -(-cfg["vocab_size"] // multiple) * multiple


def param_shapes(cfg: dict) -> dict:
    """name -> shape of every parameter, as the trainer stores them: the
    layers stacked on a leading axis, the vocabulary padded to 256."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, k = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, n = d // h, cfg["num_hidden_layers"]
    v = padded_vocab(cfg)
    shapes = {"emb/embed": (v, d), "final_norm/scale": (d,),
              "layers/attn/wq": (n, d, h, hd), "layers/attn/wk": (n, d, k, hd),
              "layers/attn/wv": (n, d, k, hd), "layers/attn/wo": (n, h, hd, d),
              "layers/ffn/w_up": (n, d, f), "layers/ffn/w_gate": (n, d, f),
              "layers/ffn/w_down": (n, f, d),
              "layers/ln1/scale": (n, d), "layers/ln2/scale": (n, d)}
    if not cfg.get("tie_word_embeddings", False):
        shapes["emb/unembed"] = (d, v)
    return shapes


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's projections and the output head at the real vocabulary; the
    embedding lookup is not a product."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, k = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    layer = d * h * hd + 2 * d * k * hd + h * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one token of a train step: 6 per matmul parameter
    (forward and backward) plus 12 * layers * d_model * seq_len for the
    attention scores and their use; recomputation is not counted."""
    return (6.0 * matmul_params(cfg)
            + 12.0 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq_len)


# ---------------------------------------------------------------------------
# the plain reference, in float32
# ---------------------------------------------------------------------------

def _dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, f=cfg["intermediate_size"], h=h,
                k=cfg["num_key_value_heads"], hd=d // h,
                n=cfg["num_hidden_layers"], v=padded_vocab(cfg),
                vocab=cfg["vocab_size"],
                eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"])


def init_params(cfg: dict, seed: int, dtype=None):
    """Initial weights: truncated normals (+-3 sigma) of std 0.02, the
    output projections of each layer at 0.02 / sqrt(2 * layers), norm
    scales 1; the layers stacked on a leading axis; the vocabulary padded
    to a multiple of 256.  The same draws the program's initializer
    makes."""
    import jax
    import jax.numpy as jnp
    dt = dtype or jnp.float32
    m = _dims(cfg)
    d, f, h, k, hd, n, v = (m[x] for x in "d f h k hd n v".split())

    def normal(key, shape, std):
        return (std * jax.random.truncated_normal(
            key, -3.0, 3.0, shape, jnp.float32)).astype(dt)

    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    ke = jax.random.split(keys[0], 2)
    out_std = 0.02 / math.sqrt(2 * n)

    def layer(key):
        ka, kf = jax.random.split(key, 2)
        qa = jax.random.split(ka, 4)
        qf = jax.random.split(kf, 3)
        return {"attn": {"wq": normal(qa[0], (d, h, hd), 0.02),
                         "wk": normal(qa[1], (d, k, hd), 0.02),
                         "wv": normal(qa[2], (d, k, hd), 0.02),
                         "wo": normal(qa[3], (h, hd, d), out_std)},
                "ffn": {"w_up": normal(qf[0], (d, f), 0.02),
                        "w_down": normal(qf[1], (f, d), out_std),
                        "w_gate": normal(qf[2], (d, f), 0.02)},
                "ln1": {"scale": jnp.ones((d,), dt)},
                "ln2": {"scale": jnp.ones((d,), dt)}}

    emb = {"embed": normal(ke[0], (v, d), 0.02)}
    if not cfg.get("tie_word_embeddings", False):
        emb["unembed"] = normal(ke[1], (d, v), 0.02)
    return {"emb": emb, "final_norm": {"scale": jnp.ones((d,), dt)},
            "layers": jax.vmap(layer)(jax.random.split(keys[1], n))}


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sequence_loss(params, tokens, labels, cfg: dict):
    """Mean cross-entropy of one sequence (tokens, labels: (S,))."""
    import jax
    import jax.numpy as jnp
    m = _dims(cfg)
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = params["emb"]["embed"][tokens]

    def layer(x, lp):
        a = _rms(x, lp["ln1"]["scale"], m["eps"])
        q = _rope(jnp.einsum("sd,dnh->snh", a, lp["attn"]["wq"]), pos,
                  m["theta"])
        k = _rope(jnp.einsum("sd,dnh->snh", a, lp["attn"]["wk"]), pos,
                  m["theta"])
        v = jnp.einsum("sd,dnh->snh", a, lp["attn"]["wv"])
        rep = m["h"] // m["k"]
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)

        # causal softmax attention over blocks of query rows, each
        # recomputed in the backward pass, so that one block's scores are
        # the largest temporary
        @jax.checkpoint
        def rows(args):
            qb, qp = args
            s = jnp.einsum("qnh,snh->nqs", qb, k) / math.sqrt(m["hd"])
            s = jnp.where((qp[:, None] >= pos[None, :])[None], s, -jnp.inf)
            return jnp.einsum("nqs,snh->qnh", jax.nn.softmax(s, axis=-1), v)

        C = min(S, QUERY_BLOCK)
        o = jax.lax.map(rows, (q.reshape(S // C, C, *q.shape[1:]),
                               pos.reshape(S // C, C)))
        o = o.reshape(q.shape)
        x = x + jnp.einsum("qnh,nhd->qd", o, lp["attn"]["wo"])
        b = _rms(x, lp["ln2"]["scale"], m["eps"])
        g = jax.nn.silu(b @ lp["ffn"]["w_gate"]) * (b @ lp["ffn"]["w_up"])
        return x + g @ lp["ffn"]["w_down"], None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], m["eps"])
    w = (params["emb"]["unembed"] if "unembed" in params["emb"]
         else params["emb"]["embed"].T)
    logits = (x @ w)[:, :m["vocab"]]          # padding rows never score
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)
