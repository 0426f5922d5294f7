"""Sharded atomic checkpoint store.

Layout (one directory per checkpoint):

    <dir>/step_00001234/
        shard_00000.npz ... shard_000HH.npz    # per-host leaf groups
        manifest.json                          # written LAST = commit marker

Atomicity: shards are written first (concurrently, on the shared
``pipeline.io_pool`` — save accepts a still-transferring chunked snapshot
and each shard worker blocks only on the chunks holding its own leaves),
then the manifest (with per-shard CRC32 checksums and the full tree spec)
is written to a temp file and renamed into place.  A checkpoint without a valid manifest (or with a
checksum mismatch) is invisible to ``newest``/``restore`` — crash-during-
write simply falls back to the previous checkpoint.

Resharding: the manifest records the leaf->shard assignment, so restore
works with any host count — each restoring host reads the files holding
its leaves.  On a real multi-host cluster each shard holds that host's
*slices*; on this single-process substrate shards hold whole leaves
(bin-packed by bytes), which exercises the same manifest-driven reshard
logic (DESIGN.md §5).
"""
from __future__ import annotations

import io
import json
import os
import shutil
import time
import zlib
from dataclasses import dataclass
from typing import Any, Callable, Optional

import numpy as np
import zstandard

from repro.utils.timing import current, span
from repro.utils.trees import tree_flatten_with_names

import jax


# ---------------------------------------------------------------------------
# Compression codecs: zstd by default, zlib when named explicitly
# ---------------------------------------------------------------------------

def resolve_codec(name: str = "auto") -> str:
    """Map a requested codec name ("auto" means zstd) to a codec."""
    if name in ("auto", "zstd"):
        return "zstd"
    if name != "zlib":
        raise ValueError(f"unknown codec {name!r}")
    return "zlib"


def get_compressor(name: str = "auto", level: int = 3
                   ) -> tuple[str, Callable[[bytes], bytes]]:
    """Returns (resolved_codec_name, compress_fn).  The resolved name must
    be recorded in the manifest so restore can pick the matching codec."""
    codec = resolve_codec(name)
    if codec == "zstd":
        # fresh context per call: the pipelined writers compress leaves
        # concurrently on the io pool and zstd contexts are not thread-safe
        return codec, lambda data: zstandard.ZstdCompressor(
            level=level).compress(data)
    return codec, lambda data: zlib.compress(data, level)


def get_decompressor(name: str) -> Callable[[bytes], bytes]:
    """Decompressor for a codec name read back from a manifest."""
    if resolve_codec(name) == "zstd":
        return lambda data: zstandard.ZstdDecompressor().decompress(data)
    return zlib.decompress


def as_leaf_dtype(v: np.ndarray, dtype) -> np.ndarray:
    """A leaf read back from an ``.npz`` as ``dtype``.  NumPy writes the
    dtypes it does not know (bfloat16 and the other ml_dtypes) as raw void
    bytes of the same width, which are reinterpreted here, not cast."""
    dtype = np.dtype(dtype)
    if v.dtype.kind == "V" and v.dtype.itemsize == dtype.itemsize:
        return v.view(dtype)
    return np.asarray(v, dtype=dtype)


# ---------------------------------------------------------------------------
# Framed compression for large single-array payloads (the flat delta
# mega-buffer): the per-leaf paths get compression parallelism for free
# (one leaf per io worker), a single big array would serialize it — so it
# is compressed as independent fixed-size frames whose compressed lengths
# are recorded in the manifest, letting the decoder split the file and
# decompress frames in parallel too.
# ---------------------------------------------------------------------------

FLAT_FRAME_BYTES = 8 << 20


def compress_frames(arr: np.ndarray, compress, pool,
                    frame_bytes: int = FLAT_FRAME_BYTES
                    ) -> tuple[list, list, float]:
    """Compress ``arr``'s bytes as independent frames, concurrently on
    ``pool``.  Returns (frames, frame_lens, cpu_s) — ``frame_lens`` goes in
    the manifest for ``decompress_frames``; ``cpu_s`` sums per-worker CPU
    seconds (the encode-cost quantity the calibration records)."""
    data = memoryview(np.ascontiguousarray(arr).reshape(-1)).cast("B")

    def one(a: int) -> tuple[bytes, float]:
        t0 = time.thread_time()
        blob = compress(bytes(data[a:a + frame_bytes]))
        return blob, time.thread_time() - t0

    futs = [pool.submit(one, a) for a in range(0, len(data), frame_bytes)]
    results = [f.result() for f in futs]
    frames = [blob for blob, _ in results]
    return frames, [len(b) for b in frames], sum(c for _, c in results)


def decompress_frames(path: str, frame_lens: list, dtype, decompress,
                      pool) -> np.ndarray:
    """Inverse of ``compress_frames``: read the file, split it on the
    recorded frame lengths, decompress frames in parallel, reassemble."""
    with open(path, "rb") as f:
        data = f.read()
    offs = [0]
    for n in frame_lens:
        offs.append(offs[-1] + int(n))
    futs = [pool.submit(decompress, data[offs[i]:offs[i + 1]])
            for i in range(len(frame_lens))]
    return np.frombuffer(b"".join(f.result() for f in futs), dtype)


# ---------------------------------------------------------------------------
# Atomic-publish helpers (shared by the full-snapshot store, the delta
# writer and anything else that commits a directory of files at once)
# ---------------------------------------------------------------------------

def write_json_atomic(path: str, obj: dict) -> None:
    """Write JSON via temp-file + rename; the rename is the commit point."""
    with open(path + ".part", "w") as f:
        json.dump(obj, f)
    os.rename(path + ".part", path)


def publish_dir_atomic(tmp: str, path: str) -> None:
    """Atomically publish a fully-written temp directory at ``path``.

    If ``path`` already exists (same step re-saved after a rollback) the old
    copy is superseded; a crash between the rmtree and the rename leaves no
    manifest at ``path`` so older checkpoints still win.
    """
    if os.path.exists(path):
        shutil.rmtree(path)
    os.rename(tmp, path)


def fresh_tmp_dir(path: str) -> str:
    """Create (or recreate) the scratch dir a checkpoint is staged in."""
    tmp = path + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    return tmp


@dataclass
class CheckpointMeta:
    step: int
    timestamp: float
    num_shards: int
    extra: dict

    @property
    def name(self) -> str:
        return f"step_{self.step:010d}"


def _crc_file(path: str) -> int:
    """CRC32 of a file's bytes, read under a ``khaos.ckpt.verify`` span."""
    with span("khaos.ckpt.verify") as sp, open(path, "rb") as f:
        data = f.read()
        sp.add(bytes_read=len(data))
        return zlib.crc32(data)


def load_npz(fpath: str, step: int, parent: Optional[int],
             wanted: Optional[set] = None) -> dict[str, np.ndarray]:
    """A shard file's leaves by name (all, or those in ``wanted``), read
    under a ``khaos.ckpt.read`` span below ``parent``."""
    with span("khaos.ckpt.read", step=step, parent=parent,
              bytes_read=os.path.getsize(fpath)), np.load(fpath) as z:
        return _leaves(z, wanted)


def read_shard(fpath: str, crc: int, step: int, parent: Optional[int],
               wanted: Optional[set] = None
               ) -> Optional[dict[str, np.ndarray]]:
    """A shard file's leaves by name (all, or those in ``wanted``), or None
    when the file is missing or unreadable or its bytes fail ``crc``.  The
    file is read once, under a ``khaos.ckpt.read`` span below ``parent``;
    the CRC (its own ``khaos.ckpt.verify`` span, no bytes counted again) is
    taken of those bytes and the arrays are built from the same bytes."""
    with span("khaos.ckpt.read", step=step, parent=parent) as sp:
        try:
            with open(fpath, "rb") as f:
                data = f.read()
        except OSError:
            return None
        sp.add(bytes_read=len(data))
        with span("khaos.ckpt.verify"):
            if zlib.crc32(data) != crc:
                return None
        with np.load(io.BytesIO(data)) as z:
            return _leaves(z, wanted)


def _leaves(z, wanted: Optional[set]) -> dict[str, np.ndarray]:
    out: dict[str, np.ndarray] = {}
    for k in z.files:
        name = k.replace("::", "/")
        if wanted is None or name in wanted:
            out[name] = z[k]
    return out


def _assign_shards(sizes_by_name: list[tuple[str, int]], num_shards: int):
    """Greedy balanced bin-packing of leaves into shards by bytes."""
    sizes = sorted(((nb, name) for name, nb in sizes_by_name), reverse=True)
    loads = [0] * num_shards
    assign: dict[str, int] = {}
    for nbytes, name in sizes:
        j = int(np.argmin(loads))
        loads[j] += nbytes
        assign[name] = j
    return assign


class CheckpointStore:
    def __init__(self, directory: str, num_shards: int = 4, keep: int = 3,
                 num_hosts: Optional[int] = None,
                 fault_hook: Optional[Callable[[str], None]] = None,
                 write_attempts: int = 4, write_backoff_s: float = 0.01):
        self.directory = directory
        self.num_shards = num_shards
        # shard j lives on simulated host ``j % num_hosts`` — the manifest
        # records this placement so failure injection can kill exactly one
        # host's files (on this substrate hosts == shards by default)
        self.num_hosts = num_hosts if num_hosts is not None else num_shards
        self.keep = keep
        # transient-IO injection point for tests: called with the target
        # path before every file write attempt; raising OSError from it
        # exercises the bounded-retry path below
        self.fault_hook = fault_hook
        self.write_attempts = write_attempts
        self.write_backoff_s = write_backoff_s
        self.saves = 0
        self.bytes_written = 0
        self.write_retries = 0
        os.makedirs(directory, exist_ok=True)

    # -- save ---------------------------------------------------------------
    def save(self, step: int, state: Any, timestamp: float = 0.0,
             extra: Optional[dict] = None) -> str:
        """Write one checkpoint.  ``state`` is a pytree or a
        ``pipeline.LeafSource`` (e.g. a chunked snapshot still transferring
        from the device): shards are planned from leaf specs alone, then
        written concurrently on the io pool — each shard worker starts as
        soon as the chunks holding its leaves have landed, overlapping D2H
        with serialization.  The manifest is written only after every shard
        has, so the commit-marker invariant is untouched."""
        from repro.checkpoint.pipeline import as_leaf_source, io_pool

        src = as_leaf_source(state)
        assign = _assign_shards([(n, src.nbytes(n)) for n in src.names],
                                self.num_shards)
        name = f"step_{step:010d}"
        path = os.path.join(self.directory, name)
        tmp = fresh_tmp_dir(path)
        parent = current()      # the shard workers' spans hang under it

        def write_shard(j: int) -> tuple[str, int]:
            from repro.checkpoint.replication import retry_with_backoff

            shard = {n.replace("/", "::"): np.asarray(src.get(n))
                     for n in src.names if assign[n] == j}
            fpath = os.path.join(tmp, f"shard_{j:05d}.npz")

            # transient IO errors (flaky disk / NFS hiccup on the remote
            # level) get bounded retries with jittered backoff instead of
            # failing the whole save; a persistent error still propagates
            # and the un-manifested .tmp dir stays invisible to restore
            def attempt() -> int:
                if self.fault_hook is not None:
                    self.fault_hook(fpath)
                with span("khaos.ckpt.write", step=step,
                          parent=parent) as sp:
                    np.savez(fpath, **shard)
                    sp.add(bytes_written=os.path.getsize(fpath))
                    return _crc_file(fpath)

            def note_retry(i: int, e: BaseException) -> None:
                self.write_retries += 1

            crc = retry_with_backoff(attempt, attempts=self.write_attempts,
                                     base_s=self.write_backoff_s,
                                     on_retry=note_retry)
            return f"shard_{j:05d}.npz", crc

        futures = [io_pool().submit(write_shard, j)
                   for j in range(self.num_shards)]
        checksums = dict(f.result() for f in futures)
        # the replica-push phase (PeerReplicatedStore) runs BETWEEN the
        # primary shard writes and the manifest commit: a failed quorum
        # raises before anything becomes visible
        replicas = self._push_replicas(tmp, checksums)

        specs = {n: src.spec(n) for n in src.names}
        manifest = {
            "step": step,
            "timestamp": timestamp,
            "num_shards": self.num_shards,
            "assign": assign,
            "checksums": checksums,
            "placement": {
                "num_hosts": self.num_hosts,
                "owners": {f: self._file_host(f) for f in checksums},
            },
            "dtypes": {n: str(dt) for n, (_, dt) in specs.items()},
            "shapes": {n: list(shape) for n, (shape, _) in specs.items()},
            "extra": extra or {},
        }
        if replicas:
            manifest["replicas"] = replicas
        write_json_atomic(os.path.join(tmp, "manifest.json"), manifest)
        publish_dir_atomic(tmp, path)
        self.saves += 1
        self.bytes_written += self.total_bytes(step)
        self._gc()
        return path

    def _push_replicas(self, tmp: str, checksums: dict) -> Optional[dict]:
        """Replication hook between shard writes and the manifest commit.
        The plain store replicates nothing (level-3 durability comes from
        the remote medium itself); ``replication.PeerReplicatedStore``
        overrides this with the ring push + quorum rule."""
        return None

    def stats(self) -> dict:
        return {"saves": self.saves, "bytes_written": self.bytes_written,
                "write_retries": self.write_retries}

    # -- host placement -------------------------------------------------------
    def _file_host(self, fname: str) -> Optional[int]:
        """Which simulated host's disk a checkpoint file lives on (None
        for files not owned by any single host, e.g. the manifest)."""
        if fname.startswith("shard_") and fname.endswith(".npz"):
            return int(fname[6:11]) % self.num_hosts
        return None

    def kill_host(self, host: int) -> list[str]:
        """Failure injection: host ``host``'s node-local disk dies, taking
        every checkpoint file placed on it (across all steps) with it.
        On the un-replicated store this leaves affected steps without a
        valid copy of the dead host's shards — exactly the degradation
        the replicated subclass exists to survive."""
        removed = []
        for name in sorted(os.listdir(self.directory)):
            d = os.path.join(self.directory, name)
            if not name.startswith("step_") or not os.path.isdir(d):
                continue
            for fname in sorted(os.listdir(d)):
                if self._file_host(fname) == host:
                    os.remove(os.path.join(d, fname))
                    removed.append(os.path.join(name, fname))
        return removed

    # -- introspection --------------------------------------------------------
    def _manifest(self, name: str) -> Optional[dict]:
        """Load a step's manifest without checksum validation."""
        mpath = os.path.join(self.directory, name, "manifest.json")
        if not os.path.exists(mpath):
            return None
        try:
            with open(mpath) as f:
                return json.load(f)
        except (json.JSONDecodeError, OSError):
            return None

    def _file_ok(self, name: str, fname: str, crc: int) -> bool:
        fpath = os.path.join(self.directory, name, fname)
        if not os.path.exists(fpath):
            return False
        return _crc_file(fpath) == crc

    def _valid(self, name: str) -> Optional[dict]:
        manifest = self._manifest(name)
        if manifest is None:
            return None
        for fname, crc in manifest["checksums"].items():
            if not self._file_ok(name, fname, crc):
                return None
        return manifest

    def _step_names(self) -> list[str]:
        return [n for n in sorted(os.listdir(self.directory))
                if n.startswith("step_") and not n.endswith(".tmp")]

    def list_steps(self) -> list[int]:
        return [int(n.split("_")[1]) for n in self._step_names()
                if self._valid(n) is not None]

    def newest(self) -> Optional[int]:
        steps = self.list_steps()
        return steps[-1] if steps else None

    def manifest_steps(self) -> list[int]:
        """Steps whose manifest loads, oldest first, with no shard read:
        the candidates a restore checks by loading them."""
        return [int(n.split("_")[1]) for n in self._step_names()
                if self._manifest(n) is not None]

    # -- restore ---------------------------------------------------------------
    def _load(self, step: int, wanted: Optional[set] = None
              ) -> tuple[dict, dict[str, np.ndarray]]:
        """(manifest, leaves by name) of ``step``, each shard file read once
        on the io pool and checked against its manifest CRC before its
        arrays are built (with ``wanted``, only the shards holding those
        leaves, and ``KeyError`` when one is not in the manifest).  Raises
        ``FileNotFoundError`` unless every shard read loads whole."""
        from repro.checkpoint.pipeline import io_pool

        name = f"step_{step:010d}"
        manifest = self._manifest(name)
        if manifest is None:
            raise FileNotFoundError(f"checkpoint {name} is corrupt or missing")
        shards = None
        if wanted is not None:
            absent = sorted(wanted - manifest["assign"].keys())
            if absent:
                raise KeyError(f"checkpoint missing leaves: {absent[:5]}...")
            shards = {f"shard_{manifest['assign'][n]:05d}.npz"
                      for n in wanted}
        parent = current()
        futs = [io_pool().submit(read_shard,
                                 os.path.join(self.directory, name, fname),
                                 crc, step, parent, wanted)
                for fname, crc in manifest["checksums"].items()
                if shards is None or fname in shards]
        parts = [f.result() for f in futs]
        if any(p is None for p in parts):
            raise FileNotFoundError(f"checkpoint {name} is corrupt or missing")
        data: dict[str, np.ndarray] = {}
        for p in parts:
            data.update(p)
        return manifest, data

    def restore(self, treedef_like: Any, step: Optional[int] = None) -> tuple[Any, dict]:
        """Restore into the structure of ``treedef_like`` (a pytree of arrays
        or ShapeDtypeStructs).  Returns (state, extra).  With no ``step``,
        the newest step that loads whole (the step ``newest()`` names)."""
        for cand in ([step] if step is not None
                     else reversed(self.manifest_steps())):
            try:
                manifest, data = self._load(cand)
                break
            except FileNotFoundError:
                if step is not None:
                    raise
        else:
            raise FileNotFoundError("no valid checkpoint found")
        names = [n for n, _ in tree_flatten_with_names(treedef_like)]
        missing = [n for n in names if n not in data]
        if missing:
            raise KeyError(f"checkpoint missing leaves: {missing[:5]}...")
        leaves_struct = jax.tree_util.tree_leaves(treedef_like)
        treedef = jax.tree_util.tree_structure(treedef_like)
        restored = [data[n] for n in names]
        restored = [as_leaf_dtype(v, s.dtype) if hasattr(s, "dtype") else v
                    for v, s in zip(restored, leaves_struct)]
        return jax.tree_util.tree_unflatten(treedef, restored), manifest["extra"]

    def read_leaves(self, step: int, names: list) -> dict[str, np.ndarray]:
        """Load only the shards holding ``names`` — the per-shard remote
        fallback of a degraded partial restore reads exactly the failed
        host's leaves, never the whole checkpoint.  Leaf names are
        layout-independent, so a remote store with a different shard
        assignment serves a local store's missing shard correctly."""
        return self._load(step, set(names))[1]

    def total_bytes(self, step: int) -> int:
        name = f"step_{step:010d}"
        p = os.path.join(self.directory, name)
        return sum(os.path.getsize(os.path.join(p, f)) for f in os.listdir(p))

    def _gc(self) -> None:
        steps = self.list_steps()
        for s in steps[:-self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:010d}"),
                          ignore_errors=True)
