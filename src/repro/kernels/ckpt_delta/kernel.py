"""Incremental-checkpoint delta codec kernels (TPU Pallas).

Two fused on-device encoders, both running as part of the async snapshot
so less (or cheaper-to-compress) data crosses the device->host link:

  * int8 (lossy): delta = new - base, per-group symmetric int8
    quantization (group = 1024 elements) — an ~3.5x cut of checkpoint
    bytes *before* host-side zstd (the level-1 codec in the multi-level
    scheme, and the same payload format the cross-pod gradient compressor
    uses).

  * lossless sub+XOR-residual: delta = new - base (fp32) plus the XOR of
    the true and predicted bit patterns (bitcast to uint32).  The
    subtraction makes slowly-drifting tensors compress hard and the
    residual makes restore BIT-exact where float rounding perturbs
    base + delta; fusing both on device removes the float math + byte-XOR
    the host CPU used to do per leaf (``ref.py`` is the host oracle and
    the fallback ``checkpoint/incremental.py`` uses off-accelerator).

Both encoders come in two granularities:

  * per-leaf (``delta_encode_fwd``/``lossless_encode_fwd``): one
    pallas_call per f32 tensor — kept as the building block of the
    per-leaf host fallback path and the dispatch-overhead baseline that
    ``benchmarks/bench_ckpt.py`` records.

  * flat (``flat_delta_encode_fwd``/``flat_lossless_encode_fwd``): ONE
    pallas_call over the packed mega-buffer the whole f32 subtree of a
    train state is flattened into (``checkpoint.pipeline.FlatLayout``:
    each leaf starts at a GROUP-aligned offset, zero-padded to a whole
    number of groups, so every group holds elements of exactly one
    leaf).  Besides the payload, the flat kernels emit per-GROUP change
    statistics in the same pass — ``group_changed`` (count of elements
    whose f32 bit pattern differs from the base) and, for lossless,
    ``group_rnnz`` (nonzero residual words) — which ``ops.py`` reduces
    to per-LEAF counts with one scatter-add over the layout's
    group->leaf map.  That is how the skip-zero manifest markers and the
    residual-D2H skip survive the fusion of N kernel launches into one.

Every kernel views its planes as (ng, GROUP) rows and walks them in
blocks of ``bg`` rows.  Mosaic tiles a block's last two dims by (8, 128),
so ``bg`` is either a multiple of 8 (the configured ``block_groups``) or
the whole row count; a row count that ``bg`` does not divide leaves a
ragged final block, whose out-of-range reads are undefined and whose
out-of-range writes are dropped — no padding copy of a state-sized plane
is made.  Per-group values (scales, change counts) travel as lane-dense
(nb, 1, bg) rows, one per block: a rank-1 (bg,) block would need bg to be
a multiple of 128 and still clash with XLA's T(1024) layout of 1-D
arrays.  The wrappers flatten those rows back to (ng,).

  new, base  (N,)        viewed as (ng, G); block (bg, G)
  q          (N,) int8   block (bg, G)          [int8 encode]
  scale      (ng,) f32   block (1, bg)          [int8 encode / decode]
  delta      (N,) f32    block (bg, G)          [lossless encode]
  resid      (N,) u32    block (bg, G)          [lossless encode]
  group_changed (ng,) i32  block (1, bg)        [flat encoders]
  group_rnnz    (ng,) i32  block (1, bg)        [flat lossless]

VMEM per step: 3-4 * bg * G fp32 (64 x 1024 -> 0.75-1 MiB), double
buffered.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

GROUP = 1024
# rows per kernel block: 64 groups x 4 f32 planes = 1 MiB of VMEM.  Small
# blocks make long grids, and Mosaic's compile time grows with the grid
BLOCK_GROUPS = 64


def _row(v: jax.Array) -> jax.Array:
    """Per-group values (bg,) -> the lane-dense (1, bg) stats row."""
    return v.reshape(1, v.shape[0])


def _col(s: jax.Array) -> jax.Array:
    """The (1, bg) stats row -> a (bg, 1) column that broadcasts over the
    GROUP lanes of each row."""
    return s.reshape(s.shape[1], 1)


def _quantize(d: jax.Array):
    scale = jnp.maximum(jnp.max(jnp.abs(d), axis=1), 1e-12) / 127.0
    q = jnp.clip(jnp.round(d / scale[:, None]), -127, 127)
    return q.astype(jnp.int8), scale


def _bits(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _count(mask: jax.Array) -> jax.Array:
    return _row(jnp.sum(mask.astype(jnp.int32), axis=1))


def _encode_kernel(new_ref, base_ref, q_ref, s_ref):
    q, scale = _quantize(new_ref[...] - base_ref[...])
    q_ref[...] = q
    s_ref[...] = _row(scale)


def _decode_kernel(q_ref, s_ref, d_ref):
    d_ref[...] = q_ref[...].astype(jnp.float32) * _col(s_ref[...])


def _lossless_encode_kernel(new_ref, base_ref, d_ref, r_ref):
    new = new_ref[...]
    base = base_ref[...]
    d = new - base
    pred = base + d          # what decode will reconstruct, same rounding
    d_ref[...] = d
    r_ref[...] = _bits(new) ^ _bits(pred)


def _lossless_decode_kernel(base_ref, d_ref, r_ref, out_ref):
    bits = _bits(base_ref[...] + d_ref[...]) ^ r_ref[...]
    out_ref[...] = jax.lax.bitcast_convert_type(bits, jnp.float32)


def _flat_lossless_encode_kernel(new_ref, base_ref, d_ref, r_ref,
                                 c_ref, n_ref):
    new = new_ref[...]
    base = base_ref[...]
    d = new - base
    pred = base + d          # what decode will reconstruct, same rounding
    r = _bits(new) ^ _bits(pred)
    d_ref[...] = d
    r_ref[...] = r
    c_ref[...] = _count(_bits(new) != _bits(base))
    n_ref[...] = _count(r != 0)


def _flat_encode_kernel(new_ref, base_ref, q_ref, s_ref, c_ref):
    new = new_ref[...]
    base = base_ref[...]
    q, scale = _quantize(new - base)
    q_ref[...] = q
    s_ref[...] = _row(scale)
    c_ref[...] = _count(_bits(new) != _bits(base))


ROWS, STAT = "rows", "stat"


def _codec_call(kernel, planes, out_dtypes, *, stat_in=None,
                block_groups: int, interpret: bool):
    """Run ``kernel`` over (ng, GROUP) ``planes`` in blocks of ``bg`` rows.

    ``out_dtypes`` is a list of (kind, dtype): ROWS outputs are (ng, GROUP)
    planes, STAT outputs one value per group, returned as (ng,).
    ``stat_in`` is an optional (ng,) per-group input (the int8 scales)."""
    ng = planes[0].shape[0]
    bg = ng if ng <= block_groups else block_groups
    assert bg == ng or bg % 8 == 0, (ng, bg)
    nb = pl.cdiv(ng, bg)
    rows = pl.BlockSpec((bg, GROUP), lambda i: (i, 0))
    stat = pl.BlockSpec((None, 1, bg), lambda i: (i, 0, 0))
    args = list(planes)
    in_specs = [rows] * len(planes)
    if stat_in is not None:
        stat_in = jnp.pad(stat_in, (0, nb * bg - ng)).reshape(nb, 1, bg)
        args.append(stat_in)
        in_specs.append(stat)
    outs = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=[rows if kind == ROWS else stat for kind, _ in out_dtypes],
        out_shape=[jax.ShapeDtypeStruct((ng, GROUP) if kind == ROWS
                                        else (nb, 1, bg), dt)
                   for kind, dt in out_dtypes],
        interpret=interpret,
    )(*args)
    return [o if kind == ROWS else o.reshape(-1)[:ng]
            for o, (kind, _) in zip(outs, out_dtypes)]


def _is_rows(x: jax.Array) -> bool:
    return x.ndim == 2 and x.shape[1] == GROUP


def _like(out: jax.Array, x: jax.Array) -> jax.Array:
    """A ROWS output in the form of the input ``x``: (ng, GROUP) rows stay
    rows, anything else gets the flat (GROUP-padded) vector."""
    return out if _is_rows(x) else out.reshape(-1)


def _as_rows(x: jax.Array, dtype) -> jax.Array:
    """``x`` as whole GROUP rows, (ng, GROUP) of ``dtype``.  A plane that
    already has that shape is used as it is; anything else is flattened
    and zero-padded.  On TPU a 1-D array and its (ng, GROUP) view tile
    differently, so that reshape is a copy of the whole plane: callers on
    the hot path keep their planes as rows."""
    if _is_rows(x):
        return x.astype(dtype)
    v = x.reshape(-1).astype(dtype)
    pad = (-v.shape[0]) % GROUP
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), dtype)])
    return v.reshape(-1, GROUP)


def _flat_rows(x: jax.Array) -> jax.Array:
    assert x.size % GROUP == 0, f"flat buffer of {x.size} not GROUP-aligned"
    return _as_rows(x, jnp.float32)


def delta_encode_fwd(new: jax.Array, base: jax.Array, *,
                     block_groups: int = BLOCK_GROUPS,
                     interpret: bool = False):
    """(new - base) -> (int8 payload padded to a multiple of GROUP, in the
    form of ``new``, per-group f32 scales); decode and slice to the leaf
    size."""
    q, s = _codec_call(
        _encode_kernel,
        [_as_rows(new, jnp.float32), _as_rows(base, jnp.float32)],
        [(ROWS, jnp.int8), (STAT, jnp.float32)],
        block_groups=block_groups, interpret=interpret)
    return _like(q, new), s


def lossless_encode_fwd(new: jax.Array, base: jax.Array, *,
                        block_groups: int = BLOCK_GROUPS,
                        interpret: bool = False):
    """Fused lossless encode: (f32 delta, u32 XOR residual), padded to a
    multiple of GROUP (zero padding encodes to zero delta + zero residual,
    so the padding compresses away)."""
    d, r = _codec_call(
        _lossless_encode_kernel,
        [_as_rows(new, jnp.float32), _as_rows(base, jnp.float32)],
        [(ROWS, jnp.float32), (ROWS, jnp.uint32)],
        block_groups=block_groups, interpret=interpret)
    return _like(d, new), _like(r, new)


def lossless_decode_fwd(base: jax.Array, delta: jax.Array, resid: jax.Array,
                        *, block_groups: int = BLOCK_GROUPS,
                        interpret: bool = False) -> jax.Array:
    """Exact inverse of ``lossless_encode_fwd`` (returns the original f32
    bit patterns, in the form of ``base``; the caller slices to the
    unpadded leaf size)."""
    (out,) = _codec_call(
        _lossless_decode_kernel,
        [_as_rows(base, jnp.float32), _as_rows(delta, jnp.float32),
         _as_rows(resid, jnp.uint32)],
        [(ROWS, jnp.float32)],
        block_groups=block_groups, interpret=interpret)
    return _like(out, base)


def delta_decode_fwd(q: jax.Array, scales: jax.Array, *,
                     block_groups: int = BLOCK_GROUPS,
                     interpret: bool = False) -> jax.Array:
    """Inverse of ``delta_encode_fwd``: the f32 delta, in the form of
    ``q`` (the caller slices to the leaf size)."""
    (d,) = _codec_call(
        _decode_kernel, [_as_rows(q, jnp.int8)], [(ROWS, jnp.float32)],
        stat_in=scales.reshape(-1).astype(jnp.float32),
        block_groups=block_groups, interpret=interpret)
    return _like(d, q)


# ---------------------------------------------------------------------------
# Flat (mega-buffer) encoders: one pallas_call over the packed f32 subtree,
# with per-group change statistics fused into the same pass
# ---------------------------------------------------------------------------

def flat_lossless_encode_fwd(new: jax.Array, base: jax.Array, *,
                             block_groups: int = BLOCK_GROUPS,
                             interpret: bool = False):
    """One fused pass over the packed flat buffer ((ng, GROUP) rows, or a
    length that ``pipeline.FlatLayout`` keeps a multiple of GROUP):
    returns (delta f32, resid u32) in the form of ``new`` and the
    per-group (group_changed i32, group_rnnz i32)."""
    d, r, c, z = _codec_call(
        _flat_lossless_encode_kernel, [_flat_rows(new), _flat_rows(base)],
        [(ROWS, jnp.float32), (ROWS, jnp.uint32), (STAT, jnp.int32),
         (STAT, jnp.int32)],
        block_groups=block_groups, interpret=interpret)
    return _like(d, new), _like(r, new), c, z


def flat_delta_encode_fwd(new: jax.Array, base: jax.Array, *,
                          block_groups: int = BLOCK_GROUPS,
                          interpret: bool = False):
    """One fused int8 pass over the packed flat buffer: returns
    (q int8 in the form of ``new``, per-group f32 scales, group_changed
    i32).  Group alignment
    means every 1024-group quantizes elements of exactly one leaf, so the
    payload is numerically identical to the per-leaf encoder's."""
    q, s, c = _codec_call(
        _flat_encode_kernel, [_flat_rows(new), _flat_rows(base)],
        [(ROWS, jnp.int8), (STAT, jnp.float32), (STAT, jnp.int32)],
        block_groups=block_groups, interpret=interpret)
    return _like(q, new), s, c
