"""Incremental (delta) checkpointing.

Between full checkpoints, only the compressed delta vs the last *full*
checkpoint is persisted — optimizer-adjacent tensors change slowly, so
deltas compress hard.  Two encodings:

  * ``lossless`` (default): delta = new - base (float32) plus an XOR
    residual between the predicted and true bytes — the subtraction makes
    slowly-drifting tensors compress hard, the residual makes restore
    BIT-exact even where float rounding perturbs the reconstruction.
    Non-float leaves store the XOR of raw bytes (zeros when unchanged).
  * ``int8``: per-group int8 quantized delta (the ``kernels/ckpt_delta``
    Pallas kernel implements the encode on-TPU; host fallback is its
    ref.py oracle).  Lossy — used as a cheap level-1 in multi-level
    schemes (paper-cited [21]); never for the level-2 full snapshots.

Compression: zstd, or stdlib zlib when the plan names it.
The codec actually used is recorded in each delta manifest so restore picks
the matching decompressor even if the environment changed in between.

Two blob layouts coexist, selected by the source:

  * per-leaf (v2, and always the host path): one ``key@suffix.bin`` blob
    set per leaf, encoded/compressed/written concurrently on the io pool.
  * flat (v3, device placement): a ``pipeline.DeltaLeafSource`` hands over
    ONE already-encoded mega-buffer payload covering its packed f32
    subtree; it is frame-compressed (``store.compress_frames``) into
    ``flat@d.bin``/``flat@r.bin`` (lossless) or ``flat@q.bin``/
    ``flat@s.bin`` (int8) and described by the manifest's ``"flat"``
    section (size, group, per-leaf layout rows, per-array frame lengths).
    Leaves outside the packed subtree still get per-leaf blobs in the
    same delta, and ``apply_delta`` restores BOTH layouts — so v2 deltas
    written before the flat path existed keep restoring unchanged.

Chain layout: full_0, delta_1..delta_{k-1}, full_k, ...; restore loads the
newest full plus its newest delta (deltas are vs the base full, not
chained, so restore reads at most two objects).

The module-level ``write_delta``/``apply_delta``/``newest_delta_step``
functions are the reusable layer: ``IncrementalCheckpointer`` (legacy API)
and ``manager.CheckpointManager`` (unified plane) both compose them.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Optional

import numpy as np

import jax

from repro.checkpoint.store import (CheckpointStore, compress_frames,
                                    decompress_frames, fresh_tmp_dir,
                                    get_compressor, get_decompressor,
                                    publish_dir_atomic, write_json_atomic)
from repro.kernels.ckpt_delta.ref import GROUP
from repro.utils.timing import span
from repro.utils.trees import tree_flatten_with_names


def delta_dir(directory: str, step: int) -> str:
    return os.path.join(directory, f"delta_{step:010d}")


def _encode_leaf_blobs(key: str, leaf: np.ndarray, b: np.ndarray,
                       mode: str, compress) -> dict[str, bytes]:
    """blob-key -> compressed payload for one leaf (runs on an io worker)."""
    blobs: dict[str, bytes] = {}
    if mode == "lossless":
        if leaf.dtype == np.float32:
            # fused sub+XOR-residual scheme — host oracle of the
            # kernels/ckpt_delta lossless Pallas kernel; identical bytes
            from repro.kernels.ckpt_delta.ref import lossless_encode_ref
            delta, resid = lossless_encode_ref(leaf, b)
            blobs[key] = compress(delta.tobytes())
            blobs[key + "::r"] = compress(resid.tobytes())
        elif np.issubdtype(leaf.dtype, np.floating):
            delta = leaf.astype(np.float32) - b.astype(np.float32)
            pred = (b.astype(np.float32) + delta).astype(leaf.dtype)
            resid = np.frombuffer(leaf.tobytes(), np.uint8) \
                ^ np.frombuffer(pred.tobytes(), np.uint8)
            blobs[key] = compress(delta.tobytes())
            blobs[key + "::r"] = compress(resid.tobytes())
        else:
            xored = np.frombuffer(leaf.tobytes(), np.uint8) \
                ^ np.frombuffer(b.tobytes(), np.uint8)
            blobs[key] = compress(xored.tobytes())
        return blobs
    # int8 group-quantized delta (host-side oracle of kernels/ckpt_delta)
    from repro.kernels.ckpt_delta.ref import encode_ref
    delta = leaf.astype(np.float32) - b.astype(np.float32)
    q, scales = encode_ref(delta.reshape(-1))
    blobs[key + "::q"] = compress(q.tobytes())
    blobs[key + "::s"] = compress(scales.tobytes())
    return blobs


def write_delta(directory: str, step: int, state_np: Any, base: Any,
                base_step: int, timestamp: float = 0.0,
                extra: Optional[dict] = None, mode: str = "lossless",
                codec: str = "auto", level: int = 3
                ) -> tuple[str, int, float]:
    """Encode + atomically publish one delta checkpoint.

    Leaves are encoded/compressed/written concurrently on the shared
    ``pipeline.io_pool``; ``state_np`` and ``base`` may be pytrees or
    ``pipeline.LeafSource``s (a chunked snapshot still transferring from
    the device overlaps its D2H with the encode of already-landed leaves).
    A ``pipeline.DeltaLeafSource`` arrives FLAT-encoded (one fused device
    kernel ran in front of D2H): its packed mega-buffer payload is
    frame-compressed and written as ``flat@*.bin`` under the manifest's
    ``"flat"`` section, its fused per-leaf change counts become ``"zero"``
    markers, and only leaves outside the packed subtree fall back to the
    per-leaf host path against ``base``.  A host-path unchanged leaf (raw
    bytes equal to the base's) is likewise recorded as a ``"zero"`` marker
    instead of compressing and writing a full-size all-zeros blob.

    Returns (path, payload_bytes, encode_cpu_s) where ``encode_cpu_s``
    sums per-worker CPU seconds spent encoding+compressing — the quantity
    ``SimCostModel.delta_encode_s_per_byte`` is calibrated from (for a
    device source this is compress-only CPU; the device encode seconds are
    measured separately by ``bench_ckpt``).  The delta manifest records
    the codec, mode and encode placement so ``apply_delta`` is
    self-describing.
    """
    from repro.checkpoint.pipeline import as_leaf_source, io_pool

    codec_name, compress = get_compressor(codec, level)
    src = as_leaf_source(state_np)
    base_src = as_leaf_source(base)
    placement = getattr(src, "placement", "host")
    layout = getattr(src, "layout", None)
    if layout is not None:
        assert getattr(src, "codec", mode) == mode, \
            (f"flat-encoded source codec {src.codec!r} does not match the "
             f"requested delta mode {mode!r}")
    packed = frozenset(layout.names) if layout is not None else frozenset()
    path = delta_dir(directory, step)
    tmp = fresh_tmp_dir(path)

    def encode_leaf(name: str) -> tuple[str, int, float, bool]:
        key = name.replace("/", "::")
        t0 = time.thread_time()
        leaf = np.asarray(src.get(name))
        b = np.asarray(base_src.get(name))
        # skip-zero fast path: byte-level equality, compared through u8
        # views (reshape keeps 0-d leaves viewable) so no copies are made
        if leaf.dtype == b.dtype and leaf.shape == b.shape and \
                np.array_equal(leaf.reshape(-1).view(np.uint8),
                               b.reshape(-1).view(np.uint8)):
            return key, 0, time.thread_time() - t0, True
        blobs = _encode_leaf_blobs(key, leaf, b, mode, compress)
        cpu_s = time.thread_time() - t0
        nbytes = 0
        for k, blob in blobs.items():
            with open(os.path.join(tmp, k.replace("::", "@") + ".bin"),
                      "wb") as f:
                f.write(blob)
            nbytes += len(blob)
        return key, nbytes, cpu_s, False

    # per-leaf host encodes for everything the flat payload doesn't cover
    futures = [io_pool().submit(encode_leaf, n) for n in src.names
               if n not in packed]

    flat_meta = None
    flat_bytes = 0
    flat_cpu = 0.0
    zero_flat: list[str] = []
    if layout is not None:
        payload = src.flat_payload()            # blocks until chunks land
        zero_flat = [n.replace("/", "::") for n in src.zero_names]
        flat_meta = {"size": layout.total, "group": GROUP,
                     "layout": [[name.replace("/", "::"), off, size, shape]
                                for name, off, size, shape
                                in layout.to_manifest()],
                     "arrays": {}}
        for sfx in (("d", "r") if mode == "lossless" else ("q", "s")):
            arr = payload.get(sfx)
            if arr is None:             # every packed leaf unchanged
                continue
            if isinstance(arr, str):    # "zero": residual D2H was skipped
                flat_meta["arrays"][sfx] = "zero"
                continue
            with span("khaos.ckpt.compress", step=step,
                      bytes_in=arr.nbytes) as sp:
                frames, lens, cpu = compress_frames(arr, compress, io_pool())
                sp.add(bytes_out=sum(lens))
            fname = f"flat@{sfx}.bin"
            with span("khaos.ckpt.write", step=step,
                      bytes_written=sum(lens)), \
                    open(os.path.join(tmp, fname), "wb") as f:
                for frame in frames:
                    f.write(frame)
            flat_meta["arrays"][sfx] = {"file": fname,
                                        "dtype": str(arr.dtype),
                                        "frames": lens}
            flat_bytes += sum(lens)
            flat_cpu += cpu

    results = [f.result() for f in futures]
    nbytes = sum(n for _, n, _, _ in results) + flat_bytes
    encode_cpu_s = sum(c for _, _, c, _ in results) + flat_cpu
    meta = {"base_step": base_step, "step": step, "timestamp": timestamp,
            "mode": mode, "codec": codec_name, "scheme": "sub+xor",
            "placement": placement,
            "zero": [k for k, _, _, z in results if z] + zero_flat,
            "extra": extra or {}}
    if flat_meta is not None:
        meta["flat"] = flat_meta
    write_json_atomic(os.path.join(tmp, "delta_manifest.json"), meta)
    publish_dir_atomic(tmp, path)
    return path, nbytes, encode_cpu_s


def read_delta_manifest(directory: str, step: int) -> Optional[dict]:
    mpath = os.path.join(delta_dir(directory, step), "delta_manifest.json")
    if not os.path.exists(mpath):
        return None
    try:
        with open(mpath) as f:
            return json.load(f)
    except (json.JSONDecodeError, OSError):
        return None


def newest_delta_step(directory: str) -> Optional[int]:
    steps = []
    for name in os.listdir(directory):
        if name.startswith("delta_") and not name.endswith(".tmp"):
            step = int(name.split("_")[1])
            if read_delta_manifest(directory, step) is not None:
                steps.append(step)
    return max(steps) if steps else None


def _decode_leaf(ddir: str, name: str, leaf: np.ndarray, mode: str,
                 xor_ints: bool, zero: frozenset, decompress,
                 device: bool = False) -> np.ndarray:
    """Read + decompress + decode one leaf (runs on an io worker).

    ``device=True`` runs the f32 decode through the ``kernels/ckpt_delta``
    Pallas kernels instead of the ref.py host oracle — bit-identical
    output (the kernels are oracle-verified), so either placement restores
    blobs written by either encoder."""
    key = name.replace("/", "@")
    if name.replace("/", "::") in zero:     # unchanged leaf: base as-is
        return leaf
    if mode == "lossless":
        with open(os.path.join(ddir, key + ".bin"), "rb") as f:
            raw = decompress(f.read())
        if leaf.dtype == np.float32:
            delta = np.frombuffer(raw, np.float32)
            rpath = os.path.join(ddir, key + "@r.bin")
            if os.path.exists(rpath):        # bit-exactness correction
                with open(rpath, "rb") as f:
                    resid = np.frombuffer(decompress(f.read()), np.uint32)
                if device:
                    from repro.kernels.ckpt_delta.ops import (
                        default_interpret, lossless_decode)
                    out = np.asarray(lossless_decode(
                        leaf.reshape(-1), delta, resid,
                        interpret=default_interpret()))[:leaf.size]
                    return out.reshape(leaf.shape)
                from repro.kernels.ckpt_delta.ref import lossless_decode_ref
                return lossless_decode_ref(leaf, delta,
                                           resid).reshape(leaf.shape)
            return (leaf.reshape(-1) + delta).reshape(leaf.shape)
        if np.issubdtype(leaf.dtype, np.floating):
            delta = np.frombuffer(raw, np.float32).reshape(leaf.shape)
            pred = (leaf.astype(np.float32) + delta).astype(leaf.dtype)
            rpath = os.path.join(ddir, key + "@r.bin")
            if os.path.exists(rpath):        # bit-exactness correction
                with open(rpath, "rb") as f:
                    resid = np.frombuffer(decompress(f.read()), np.uint8)
                exact = np.frombuffer(pred.tobytes(), np.uint8) ^ resid
                pred = np.frombuffer(exact.tobytes(),
                                     leaf.dtype).reshape(leaf.shape)
            return pred
        if xor_ints:
            xored = np.frombuffer(raw, np.uint8)
            base_b = np.frombuffer(leaf.tobytes(), np.uint8)
            return np.frombuffer((xored ^ base_b).tobytes(),
                                 leaf.dtype).reshape(leaf.shape)
        # legacy scheme stored the raw leaf bytes
        return np.frombuffer(raw, leaf.dtype).reshape(leaf.shape)
    with open(os.path.join(ddir, key + "@q.bin"), "rb") as f:
        q = np.frombuffer(decompress(f.read()), np.int8)
    with open(os.path.join(ddir, key + "@s.bin"), "rb") as f:
        s = np.frombuffer(decompress(f.read()), np.float32)
    if device:
        from repro.kernels.ckpt_delta.ops import (default_interpret,
                                                  delta_decode)
        delta = np.asarray(delta_decode(
            q, s, interpret=default_interpret()))[:leaf.size]
        delta = delta.reshape(leaf.shape)
    else:
        from repro.kernels.ckpt_delta.ref import decode_ref
        delta = decode_ref(q, s)[:leaf.size].reshape(leaf.shape)
    return (leaf.astype(np.float32) + delta).astype(leaf.dtype)


def _decode_flat(ddir: str, flat: dict, mode: str, zero: frozenset,
                 base_leaves: dict, decompress, device: bool) -> dict:
    """Decode the flat mega-buffer payload back into per-leaf arrays.

    Rebuilds the packed base from the restored base leaves (host-side,
    matching ``FlatLayout``'s GROUP-aligned zero-padding), applies the
    flat delta — sub+XOR-residual or int8 dequant, through the Pallas
    kernels when ``device=True``, the ref.py oracles otherwise — in ONE
    vectorized pass, then slices each leaf back out by its manifest
    extent.  Leaves in ``zero`` take the base as-is.  Returns
    {name: decoded array} for every packed leaf."""
    entries = [(key.replace("::", "/"), int(off), int(size), tuple(shape))
               for key, off, size, shape in flat["layout"]]
    from repro.checkpoint.pipeline import io_pool
    arrays: dict[str, np.ndarray] = {}
    for sfx, spec in flat.get("arrays", {}).items():
        if spec == "zero":
            continue
        arrays[sfx] = decompress_frames(
            os.path.join(ddir, spec["file"]), spec["frames"],
            np.dtype(spec["dtype"]), decompress, io_pool())
    if not arrays:                  # every packed leaf was unchanged
        return {name: base_leaves[name] for name, _, _, _ in entries}
    total = int(flat["size"])
    base_flat = np.zeros(total, np.float32)
    for name, off, size, _ in entries:
        base_flat[off:off + size] = np.ascontiguousarray(
            base_leaves[name], np.float32).reshape(-1)
    if mode == "lossless":
        delta = arrays["d"]
        resid = arrays.get("r")
        if resid is None:           # skipped all-zero residual plane
            resid = np.zeros(total, np.uint32)
        if device:
            from repro.kernels.ckpt_delta.ops import (default_interpret,
                                                      lossless_decode)
            rows = (-1, GROUP)      # the kernels' layout: no relayout
            out_flat = np.asarray(lossless_decode(
                base_flat.reshape(rows), delta.reshape(rows),
                resid.reshape(rows),
                interpret=default_interpret())).reshape(-1)[:total]
        else:
            from repro.kernels.ckpt_delta.ref import lossless_decode_ref
            out_flat = lossless_decode_ref(base_flat, delta, resid)
    else:
        if device:
            from repro.kernels.ckpt_delta.ops import (default_interpret,
                                                      delta_decode)
            dflat = np.asarray(delta_decode(
                arrays["q"].reshape(-1, GROUP), arrays["s"],
                interpret=default_interpret())).reshape(-1)[:total]
        else:
            from repro.kernels.ckpt_delta.ref import decode_ref
            dflat = decode_ref(arrays["q"], arrays["s"])[:total]
        out_flat = base_flat + dflat
    out: dict[str, np.ndarray] = {}
    for name, off, size, shape in entries:
        if name.replace("/", "::") in zero:
            out[name] = base_leaves[name]       # unchanged: base as-is
        else:
            out[name] = out_flat[off:off + size].reshape(shape)
    return out


def apply_delta(directory: str, step: int, base_state: Any,
                placement: str = "host") -> Any:
    """Apply the delta at ``step`` on top of ``base_state`` (the restored
    base full snapshot).  Codec and mode come from the delta manifest; the
    flat mega-buffer section (if present) decodes in one vectorized pass
    and the remaining per-leaf blobs decode concurrently (mirror of the
    pipelined write path) — so v3 flat deltas, v2 per-leaf deltas, and
    mixed deltas all restore through this one reader.

    ``placement`` selects where the DECODE runs ("host" via ref.py, or
    "device" via the Pallas kernels) and is independent of the placement
    the delta was encoded with — blobs are byte-compatible both ways, so
    a host-encoded checkpoint restores through the device path and vice
    versa."""
    assert placement in ("host", "device"), placement
    meta = read_delta_manifest(directory, step)
    if meta is None:
        raise FileNotFoundError(f"delta {step} is corrupt or missing")
    # pre-refactor manifests carry no codec/scheme/zero fields: they were
    # written with the then-unconditional zstd, float deltas had no XOR
    # residual (handled below by the missing @r.bin) and non-float leaves
    # stored raw bytes rather than an XOR vs the base
    decompress = get_decompressor(meta.get("codec", "zstd"))
    mode = meta.get("mode", "lossless")
    xor_ints = meta.get("scheme") == "sub+xor"
    zero = frozenset(meta.get("zero", ()))
    ddir = delta_dir(directory, step)
    names = [n for n, _ in tree_flatten_with_names(base_state)]
    leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(base_state)]
    flat_out: dict[str, np.ndarray] = {}
    flat = meta.get("flat")
    if flat:
        flat_out = _decode_flat(ddir, flat, mode, zero,
                                dict(zip(names, leaves)), decompress,
                                placement == "device")
    from repro.checkpoint.pipeline import io_pool
    futures = {name: io_pool().submit(_decode_leaf, ddir, name, leaf, mode,
                                      xor_ints, zero, decompress,
                                      placement == "device")
               for name, leaf in zip(names, leaves) if name not in flat_out}
    out = [flat_out[name] if name in flat_out else futures[name].result()
           for name in names]
    treedef = jax.tree_util.tree_structure(base_state)
    return jax.tree_util.tree_unflatten(treedef, out)


class IncrementalCheckpointer:
    def __init__(self, store: CheckpointStore, full_every: int = 8,
                 mode: str = "lossless", zstd_level: int = 3,
                 codec: str = "auto"):
        assert mode in ("lossless", "int8")
        self.store = store
        self.full_every = full_every
        self.mode = mode
        self.codec = codec
        self.zstd_level = zstd_level
        self._count = 0
        self._base: Optional[Any] = None
        self._base_step: Optional[int] = None
        self.bytes_written_full = 0
        self.bytes_written_delta = 0
        # pre-compression, post-encode bytes (this legacy checkpointer is
        # host-encode only, so every save moves the raw state D2H) — kept
        # separate from the post-compression bytes above so the BENCH
        # artifacts and the cost model don't conflate link and disk traffic
        self.bytes_on_link = 0

    # ------------------------------------------------------------------
    def save(self, step: int, state: Any, timestamp: float = 0.0,
             extra: Optional[dict] = None) -> str:
        state_np = jax.tree_util.tree_map(np.asarray, state)
        self.bytes_on_link += sum(l.nbytes for l in
                                  jax.tree_util.tree_leaves(state_np))
        if self._count % self.full_every == 0 or self._base is None:
            path = self.store.save(step, state_np, timestamp,
                                   {**(extra or {}), "kind": "full"})
            self._base = state_np
            self._base_step = step
            self.bytes_written_full += self.store.total_bytes(step)
        else:
            path, nbytes, _ = write_delta(
                self.store.directory, step, state_np, self._base,
                self._base_step, timestamp, extra or {}, self.mode,
                self.codec, self.zstd_level)
            self.bytes_written_delta += nbytes
        self._count += 1
        return path

    # ------------------------------------------------------------------
    def newest_delta(self) -> Optional[int]:
        return newest_delta_step(self.store.directory)

    def restore(self, treedef_like: Any) -> tuple[Any, int]:
        """Restore newest state (full + newest applicable delta).
        Returns (state, step)."""
        full_step = self.store.newest()
        if full_step is None:
            raise FileNotFoundError("no full checkpoint")
        state, _ = self.store.restore(treedef_like, full_step)
        dstep = self.newest_delta()
        if dstep is None or dstep <= full_step:
            return state, full_step
        meta = read_delta_manifest(self.store.directory, dstep)
        if meta is None or meta["base_step"] != full_step:
            return state, full_step   # delta belongs to an older chain
        return apply_delta(self.store.directory, dstep, state), dstep

    def stats(self) -> dict:
        return {"saves": self._count,
                "bytes_written_full": self.bytes_written_full,
                "bytes_written_delta": self.bytes_written_delta,
                "bytes_on_link": self.bytes_on_link}
