"""Public jit'd wrappers for the delta codec kernels.

Three tiers of entry points:

  * whole-buffer codec ops (``delta_encode``/``lossless_decode``/...):
    shape-generic, used by the host<->device decode paths.

  * per-leaf fused ops (``lossless_encode_leaf``/``int8_encode_leaf``):
    encode + unchanged-leaf detection + residual-sparsity count in one
    jitted call per leaf.  The pre-flat device delta plane dispatched
    these once per f32 leaf; they remain as the host-fallback building
    block and the dispatch-overhead baseline ``bench_ckpt`` records
    (``per_leaf_encode_s`` in the bench_ckpt/3 artifact).

  * flat fused ops (``pack_flat``/``flat_lossless_encode``/
    ``flat_int8_encode``): the hot path.  ``pack_flat`` concatenates the
    f32 subtree into ONE GROUP-aligned device mega-buffer (one jitted
    dispatch); the flat encoders run ONE pallas_call over it and reduce
    the kernel's per-group change statistics to per-LEAF counts with a
    scatter-add over the layout's group->leaf map — all inside the same
    jit, so a delta trigger costs one pack dispatch + one encode dispatch
    regardless of how many hundreds of leaves the state has.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ckpt_delta.kernel import (BLOCK_GROUPS, GROUP,
                                             delta_decode_fwd,
                                             delta_encode_fwd,
                                             flat_delta_encode_fwd,
                                             flat_lossless_encode_fwd,
                                             lossless_decode_fwd,
                                             lossless_encode_fwd)


def default_interpret() -> bool:
    """Pallas interpret mode is required off-accelerator (the CPU backend
    has no Mosaic lowering); on TPU the compiled kernels run."""
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("block_groups", "interpret"))
def delta_encode(new, base, *, block_groups: int = BLOCK_GROUPS,
                 interpret: bool = False):
    """(new - base) -> (int8 payload, per-1024-group fp32 scales)."""
    return delta_encode_fwd(new, base, block_groups=block_groups,
                            interpret=interpret)


@partial(jax.jit, static_argnames=("block_groups", "interpret"))
def delta_decode(q, scales, *, block_groups: int = BLOCK_GROUPS,
                 interpret: bool = False):
    """Inverse of delta_encode (returns fp32 delta)."""
    return delta_decode_fwd(q, scales, block_groups=block_groups,
                            interpret=interpret)


@partial(jax.jit, static_argnames=("block_groups", "interpret"))
def lossless_encode(new, base, *, block_groups: int = BLOCK_GROUPS,
                    interpret: bool = False):
    """Fused lossless encode: (f32 delta, u32 XOR residual) vs base."""
    return lossless_encode_fwd(new, base, block_groups=block_groups,
                               interpret=interpret)


@partial(jax.jit, static_argnames=("block_groups", "interpret"))
def lossless_decode(base, delta, resid, *, block_groups: int = BLOCK_GROUPS,
                    interpret: bool = False):
    """Bit-exact inverse of lossless_encode (returns the original f32)."""
    return lossless_decode_fwd(base, delta, resid, block_groups=block_groups,
                               interpret=interpret)


# ---------------------------------------------------------------------------
# Per-leaf fused entry points for the device-resident delta plane
# ---------------------------------------------------------------------------

def _bits_changed(new_f32: jax.Array, base_f32: jax.Array) -> jax.Array:
    """True iff any f32 bit pattern differs — the device twin of the host
    path's raw-byte equality check that gates the manifest "zero" marker."""
    return jnp.any(jax.lax.bitcast_convert_type(new_f32, jnp.uint32)
                   != jax.lax.bitcast_convert_type(base_f32, jnp.uint32))


@partial(jax.jit, static_argnames=("block_groups", "interpret"))
def lossless_encode_leaf(new, base, *, block_groups: int = BLOCK_GROUPS,
                         interpret: bool = False):
    """One leaf's on-device lossless encode: (delta f32, resid u32 — both
    GROUP-padded), plus ``changed`` (any bit differs -> leaf must be
    written) and ``resid_nnz`` (nonzero residual words).  The residual is
    almost always all-zero (base + delta rounds back exactly whenever
    new/base are within 2x of each other), so the caller skips its D2H
    when ``resid_nnz == 0`` and reconstructs zeros host-side — the blob on
    disk stays byte-identical to the host encoder's."""
    nf = new.reshape(-1).astype(jnp.float32)
    bf = base.reshape(-1).astype(jnp.float32)
    d, r = lossless_encode_fwd(nf, bf, block_groups=block_groups,
                               interpret=interpret)
    return d, r, _bits_changed(nf, bf), jnp.count_nonzero(r)


@partial(jax.jit, static_argnames=("block_groups", "interpret"))
def int8_encode_leaf(new, base, *, block_groups: int = BLOCK_GROUPS,
                     interpret: bool = False):
    """One leaf's on-device int8 group-quantized delta encode: (q int8
    GROUP-padded, per-group f32 scales, changed).  Worst-case error per
    element is half a quantization step: |err| <= max|delta_group| / 254
    (scale = amax/127, round-to-nearest) — the documented bound the
    round-trip test asserts."""
    nf = new.reshape(-1).astype(jnp.float32)
    bf = base.reshape(-1).astype(jnp.float32)
    q, s = delta_encode_fwd(nf, bf, block_groups=block_groups,
                            interpret=interpret)
    return q, s, _bits_changed(nf, bf)


# ---------------------------------------------------------------------------
# Flat (mega-buffer) entry points for the packed device delta plane
# ---------------------------------------------------------------------------

@jax.jit
def pack_flat(leaves):
    """Pack a sequence of f32 leaves into ONE flat device buffer of
    (ng, GROUP) rows, each leaf zero-padded to a whole number of GROUPs
    so it starts at a GROUP-aligned offset (``pipeline.FlatLayout``
    records the offsets).  The buffer is built as rows because on TPU a
    1-D array and its (ng, GROUP) view tile differently: the encoders
    then read it without a relayout copy.  Each leaf is written into a
    zeroed buffer in place (a concatenate of the per-leaf rows would
    first materialize every relayouted leaf: another state-sized
    buffer).  One dispatch for the whole subtree; the checkpoint
    pipeline compiles it ahead of time per layout (leaf shape set)."""
    rows = [-(-leaf.size // GROUP) for leaf in leaves]
    out = jnp.zeros((sum(rows), GROUP), jnp.float32)
    at = 0
    for leaf, n in zip(leaves, rows):
        v = leaf.reshape(-1).astype(jnp.float32)
        v = jnp.pad(v, (0, n * GROUP - v.shape[0])).reshape(n, GROUP)
        out = jax.lax.dynamic_update_slice(out, v, (at, 0))
        at += n
    return out


def _flat_block_groups(n: int, block_groups: int, interpret: bool) -> int:
    """Kernel block size for an ``n``-element flat buffer.  Interpret mode
    (CPU backend) pays per grid STEP — each step re-slices the full
    operands — so there the whole buffer becomes ONE block (no VMEM bound
    applies off-accelerator); compiled mode keeps ``block_groups`` and
    lets the final block run ragged."""
    return max(1, n // GROUP) if interpret else block_groups


@partial(jax.jit, static_argnames=("num_leaves", "block_groups", "interpret"))
def flat_lossless_encode(new_flat, base_flat, group_leaf, *, num_leaves: int,
                         block_groups: int = BLOCK_GROUPS,
                         interpret: bool = False):
    """Fused lossless encode of the packed mega-buffer (``pack_flat``'s
    rows): ONE pallas_call emits (delta f32, resid u32), in the form of
    ``new_flat``, plus per-group change stats, and a
    scatter-add over ``group_leaf`` (the layout's group->leaf index map)
    reduces them to per-LEAF counts — returns (delta, resid,
    leaf_changed i32[num_leaves], leaf_rnnz i32[num_leaves]).  A leaf
    with ``leaf_changed == 0`` is bit-identical to the base (the skip-zero
    manifest marker); ``leaf_rnnz.sum() == 0`` means the residual plane is
    all-zero and its D2H can be skipped entirely."""
    d, r, gc, gz = flat_lossless_encode_fwd(
        new_flat, base_flat, interpret=interpret,
        block_groups=_flat_block_groups(new_flat.size, block_groups,
                                        interpret))
    leaf_changed = jnp.zeros((num_leaves,), jnp.int32).at[group_leaf].add(gc)
    leaf_rnnz = jnp.zeros((num_leaves,), jnp.int32).at[group_leaf].add(gz)
    return d, r, leaf_changed, leaf_rnnz


@partial(jax.jit, static_argnames=("num_leaves", "block_groups", "interpret"))
def flat_int8_encode(new_flat, base_flat, group_leaf, *, num_leaves: int,
                     block_groups: int = BLOCK_GROUPS,
                     interpret: bool = False):
    """Fused int8 encode of the packed mega-buffer: ONE pallas_call emits
    (q int8 in the form of ``new_flat``, per-1024-group f32 scales) plus
    per-group change counts,
    reduced to per-leaf via scatter-add — returns (q, scales,
    leaf_changed i32[num_leaves]).  Group alignment keeps every scale
    group within a single leaf, so the payload matches the per-leaf
    encoder's bit for bit."""
    q, s, gc = flat_delta_encode_fwd(
        new_flat, base_flat, interpret=interpret,
        block_groups=_flat_block_groups(new_flat.size, block_groups,
                                        interpret))
    leaf_changed = jnp.zeros((num_leaves,), jnp.int32).at[group_leaf].add(gc)
    return q, s, leaf_changed
