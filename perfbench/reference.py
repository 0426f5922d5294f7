"""Plain references the benchmark holds the program to.  Nothing here
imports the program or takes anything it made.

  * The first steps of training a configuration at its cut, in float32
    with matrix products at ``highest`` precision: the model's initial
    weights from the seed and its mean token cross-entropy come from the
    configuration's architecture module (``archs/<arch>.py``), which
    imports nothing of the program either; here are the gradients, and
    AdamW with global-norm clipping, warmup and cosine decay as the
    configuration states them.  A batch is processed one sequence at a
    time, so that the reference fits beside nothing.
  * The events' tokens: event ``o`` is a sequence of ``seq_len + 1``
    tokens, t0 drawn from the seed and the offset, then
    t_{i+1} = (31 t_i + 7) mod vocab.
  * The int8 delta codec (per 1024-element group, scale = max|d| / 127,
    q = round(d / scale) clipped to +-127) and, as the control, the same
    codec with 4-bit codes.
  * Readers of the files a checkpoint directory holds: a full save's
    ``.npz`` shards and a flat delta's planes, and the bit sums and group
    samples a state is compared by.
"""
from __future__ import annotations

import json
import math
import os
from functools import partial

import numpy as np

GROUP = 1024


# ---------------------------------------------------------------------------
# events -> tokens
# ---------------------------------------------------------------------------

def event_tokens(offsets, seq_len: int, vocab: int, seed: int) -> np.ndarray:
    """(len(offsets), seq_len + 1) int32 tokens of the given events."""
    out = np.empty((len(offsets), seq_len + 1), np.int64)
    for i, off in enumerate(offsets):
        rng = np.random.default_rng(np.uint64(seed * 2654435761 + int(off)))
        out[i, 0] = rng.integers(0, vocab)
    for j in range(1, seq_len + 1):
        out[:, j] = (31 * out[:, j - 1] + 7) % vocab
    return out.astype(np.int32)


# ---------------------------------------------------------------------------
# the first steps of training
# ---------------------------------------------------------------------------

def lr_at(step: int, opt: dict) -> float:
    warm = max(1, opt["warmup_steps"])
    total = max(opt["total_steps"], warm + 1)
    base = opt["lr"] * min(1.0, step / warm)
    if opt["schedule"] == "constant" or step < warm:
        return base
    prog = min(1.0, max(0.0, (step - warm) / (total - warm)))
    return opt["lr"] * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * prog)))


def _adamw_leaf(p, g, m, v, lr, clip, bc1, bc2, *, b1, b2, eps, wd):
    import jax.numpy as jnp
    g = g * clip
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    delta = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    return p - lr * (delta + wd * p), m, v, jnp.sqrt(jnp.sum(g * g))


def first_steps(cfg: dict, seed: int, batches: list, dtype=None, *,
                arch) -> dict:
    """The first ``len(batches)`` steps of the architecture module
    ``arch`` (its ``init_params`` and ``sequence_loss``) from the seed's
    weights: each step's loss, the per-leaf norm of the first clipped
    gradient, and the per-leaf norm of the parameters' change after the
    last step.  A step is the mean loss and gradient over the batch's
    sequences, global-norm clipping, then AdamW (decay on every leaf of two
    or more dimensions).  Leaves are updated one at a time in place, so
    the parameters, both moments, one gradient and the running sum are
    what the device holds besides one sequence's activations."""
    import jax
    import jax.numpy as jnp
    opt = cfg["job"]["optimizer"]
    b1, b2 = opt["b1"], opt["b2"]
    with jax.default_matmul_precision("highest"):
        params = arch.init_params(cfg, seed, dtype)
        names = list(leaf_norms_names(params))
        P, treedef = jax.tree_util.tree_flatten(params)
        del params
        M = V = None
        grad = jax.jit(jax.value_and_grad(partial(arch.sequence_loss,
                                                  cfg=cfg)))
        add = jax.jit(jnp.add, donate_argnums=0)
        scale = jax.jit(jnp.multiply, donate_argnums=0)
        sumsq = jax.jit(lambda x: jnp.sum(x * x))
        upd = {wd: jax.jit(partial(_adamw_leaf, b1=b1, b2=b2,
                                   eps=opt["eps"], wd=wd),
                           donate_argnums=(0, 2, 3))
               for wd in {0.0, opt["weight_decay"]}}
        losses, grad1 = [], None
        for i, (tok, lab) in enumerate(batches):
            loss, acc = 0.0, None
            for b in range(tok.shape[0]):
                l, g = grad(jax.tree_util.tree_unflatten(treedef, P),
                            jnp.asarray(tok[b]), jnp.asarray(lab[b]))
                g = jax.tree_util.tree_leaves(g)
                acc = g if acc is None else [add(a, x)
                                             for a, x in zip(acc, g)]
                del g
                loss = loss + float(l)
            n = tok.shape[0]
            acc = [scale(a, jnp.float32(1.0 / n)) for a in acc]
            gn = math.sqrt(sum(float(sumsq(a)) for a in acc))
            clip = min(1.0, opt["grad_clip"] / max(gn, 1e-9))
            if M is None:
                M = [jnp.zeros_like(p) for p in P]
                V = [jnp.zeros_like(p) for p in P]
            t = i + 1.0
            norms = []
            for j in range(len(P)):
                wd = opt["weight_decay"] if P[j].ndim >= 2 else 0.0
                P[j], M[j], V[j], gnorm = upd[wd](
                    P[j], acc[j], M[j], V[j], jnp.float32(lr_at(i, opt)),
                    jnp.float32(clip), jnp.float32(1.0 - b1 ** t),
                    jnp.float32(1.0 - b2 ** t))
                acc[j] = None
                norms.append(float(gnorm))
            losses.append(loss / n)
            if grad1 is None:
                grad1 = dict(zip(names, norms))
        del M, V, acc
        p0 = jax.tree_util.tree_leaves(arch.init_params(cfg, seed, dtype))
        change = {}
        for j, name in enumerate(names):
            change[name] = math.sqrt(float(sumsq(P[j] - p0[j])))
            p0[j] = None
    return {"losses": losses, "grad1": grad1, "change": change}


def leaf_norms_names(tree) -> list:
    """'a/b/c' names of a tree's leaves, in tree order."""
    import jax
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


# ---------------------------------------------------------------------------
# the int8 delta codec, and its 4-bit control
# ---------------------------------------------------------------------------

def encode_groups(d: np.ndarray, bits: int = 8) -> tuple[np.ndarray,
                                                         np.ndarray]:
    """Quantize (g, 1024) f32 deltas with a symmetric ``bits``-bit code
    per group.  Returns (q, scales)."""
    top = 2 ** (bits - 1) - 1
    d = np.asarray(d, np.float32)
    scale = (np.maximum(np.abs(d).max(axis=1), np.float32(1e-12))
             / np.float32(top)).astype(np.float32)
    q = np.clip(np.round(d / scale[:, None]), -top, top)
    return q.astype(np.int8), scale


def decode_groups(q: np.ndarray, scales: np.ndarray) -> np.ndarray:
    return q.astype(np.float32) * np.asarray(scales, np.float32)[:, None]


# half a quantization step plus the float32 roundings of d / scale and
# q * scale, each at most 127 * 2**-24 of a scale
INT8_SLACK = 254 * 2.0 ** -24


def int8_error_ratio(decoded: np.ndarray, delta: np.ndarray) -> float:
    """Worst |decoded - delta| over the codec's stated bound, per group:
    scale * (1/2 + the float32 roundings), scale = max|delta| / 127 taken
    in float64 from the exact delta."""
    delta = np.asarray(delta, np.float64)
    scale = np.maximum(np.abs(delta).max(axis=1), 1e-12) / 127.0
    err = np.abs(np.asarray(decoded, np.float64) - delta)
    return float(np.max(err / (scale[:, None] * (0.5 + INT8_SLACK))))


# ---------------------------------------------------------------------------
# reading the flat int8 delta a checkpoint directory holds
# ---------------------------------------------------------------------------

def read_flat_delta(delta_dir: str) -> dict:
    """The manifest of a delta directory and a reader of its flat q/s
    planes: {"manifest", "layout": name -> (offset, size, shape),
    "rows": fn(group_rows) -> (q (r, 1024) int8, s (r,) f32)}."""
    import zstandard
    with open(os.path.join(delta_dir, "delta_manifest.json")) as f:
        man = json.load(f)
    flat = man["flat"]
    if man.get("codec", "zstd") != "zstd":
        raise ValueError(f"unexpected codec {man.get('codec')}")

    def plane(sfx: str) -> np.ndarray:
        spec = flat["arrays"][sfx]
        with open(os.path.join(delta_dir, spec["file"]), "rb") as f:
            data = f.read()
        parts, a = [], 0
        dec = zstandard.ZstdDecompressor()
        for n in spec["frames"]:
            parts.append(dec.decompress(data[a:a + n]))
            a += n
        return np.frombuffer(b"".join(parts), np.dtype(spec["dtype"]))

    q = plane("q").reshape(-1, flat["group"])
    s = plane("s")
    layout = {name.replace("::", "/"): (off, size, tuple(shape))
              for name, off, size, shape in flat["layout"]}
    return {"manifest": man, "layout": layout, "q": q, "s": s}


# ---------------------------------------------------------------------------
# reading a full save, and what a state is compared by
# ---------------------------------------------------------------------------

def read_full_save(step_dir: str) -> tuple[dict, dict]:
    """The manifest of a full save's directory and its leaves, name ->
    array, read from every ``.npz`` shard the manifest lists."""
    with open(os.path.join(step_dir, "manifest.json")) as f:
        man = json.load(f)
    leaves: dict = {}
    for fname in man["checksums"]:
        with np.load(os.path.join(step_dir, fname)) as z:
            for key in z.files:
                leaves[key.replace("::", "/")] = z[key]
    for name, shape in man["shapes"].items():
        if tuple(leaves[name].shape) != tuple(shape):
            raise ValueError(f"{name}: shape {leaves[name].shape} != {shape}")
    return man, leaves


def bit_digest(x: np.ndarray) -> np.ndarray:
    """Two uint32 sums of a leaf's bits, modulo 2**32: plain, and each
    element weighted by (row-major position * 2654435761 + 1)."""
    x = np.ascontiguousarray(x).reshape(-1)
    if x.dtype.itemsize == 4:
        b = x.view(np.uint32)
    elif x.dtype.itemsize == 2:
        b = x.view(np.uint16).astype(np.uint32)
    else:
        b = x.astype(np.uint32)
    w = np.arange(b.size, dtype=np.uint32) * np.uint32(2654435761) \
        + np.uint32(1)
    return np.array([np.sum(b, dtype=np.uint32),
                     np.sum(b * w, dtype=np.uint32)], np.uint32)


def group_samples(leaves: list, grps: list) -> np.ndarray:
    """(len(grps), 1024) float32: group ``g`` of each leaf, as the codec
    groups a flattened leaf, zero past the leaf's end."""
    out = np.zeros((len(grps), GROUP), np.float32)
    for k, (x, g) in enumerate(zip(leaves, grps)):
        flat = np.asarray(x).reshape(-1)[g * GROUP:(g + 1) * GROUP]
        out[k, :flat.size] = flat
    return out
