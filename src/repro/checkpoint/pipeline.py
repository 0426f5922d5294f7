"""Pipelined checkpoint hot path: chunked device->host transfer feeding a
parallel compression/write worker pool, with the delta encode placeable on
EITHER side of the link.

The pre-pipeline save path was serial end-to-end: a monolithic
``snapshot_to_host`` deep copy of the whole state blocked the step stream,
then every leaf was encoded and compressed one after another on the commit
thread.  This module breaks that into overlapping stages.  Host placement
(``CheckpointPlan.encode_placement="host"``, the default) ships the raw
state and encodes behind the link:

    trigger -> chunked D2H transfer  ||  encode  ||  compress  ||  write

Device placement runs the ``kernels/ckpt_delta`` codec in front of D2H
(``DeltaLeafSource``), so only the encoded payload crosses the link —
delta + sparse residual (lossless) or int8 q + scales (~4x fewer bytes):

    trigger -> pack -> ONE fused encode -> chunked D2H of encoded payload
                                                 ||  compress  ||  write

The device encode is FLAT: the f32 subtree of the state is packed into
one contiguous GROUP-aligned mega-buffer (``FlatLayout`` — each leaf
zero-padded to a whole number of 1024-element groups, so per-group change
statistics map exactly onto leaves), diffed against the equally-packed
``DeviceDeltaBase.flat`` by a single ``flat_lossless_encode``/
``flat_int8_encode`` dispatch, and the encoded payload streams off-device
in byte-bounded chunks.  One pack dispatch + one encode dispatch + one
chunked transfer replace the N per-leaf kernel launches + N small D2H
copies the pre-flat plane paid (which priced device placement out of the
optimizer on seconds while winning on bytes).

Flat-layout manifest (the ``"flat"`` section ``incremental.write_delta``
records, decoded by ``incremental.apply_delta``):

    {"size": <padded elems>, "group": 1024,
     "layout": [[name, offset, size, shape], ...],   # GROUP-aligned offsets
     "arrays": {"d": {file, dtype, frames}, "r": "zero" | {...}}}

plus per-leaf skip-zero markers in the manifest's ``zero`` list (from the
kernel's fused per-leaf change counts) and a ``"zero"`` marker for an
all-zero residual plane whose D2H was skipped entirely.  Leaves outside
the packed subtree (non-f32, host-resident, shape-drifted) fall back to
the per-leaf host encode path and per-leaf blobs — a v3 (flat) delta can
carry both, and per-leaf-only v2 deltas keep restoring through the same
reader.

Pack/refresh lifecycle: ``DeviceDeltaBase`` packs its flat buffer ONCE
per full trigger/savepoint (``CheckpointManager`` refreshes it there and
carries it across ``set_plan`` rebuilds via ``adopt_runtime_state``);
every delta trigger then packs only the NEW state (one dispatch) and
encodes against the resident base, so the steady-state trigger never
re-uploads or re-packs the base.  The pack and the encoder are compiled
ahead of time per layout (``prepare_device_encode``, which
``CheckpointManager.prepare`` runs before a job's clock starts), so no
trigger's blocking time includes a compile.

  * ``ChunkedHostSnapshot`` partitions the state's leaves into byte-bounded
    chunks.  Mutable host leaves (``np.ndarray``) are deep-copied eagerly —
    the caller may mutate them in place the moment ``save()`` returns, so
    their copy IS the blocking cost (this is the aliasing hazard the
    pipeline must preserve; see the race test in test_checkpoint_plane).
    Immutable ``jax.Array`` leaves only need their references grabbed: the
    first chunk is materialized synchronously (the device sync), the rest
    transfer on a background pool while downstream encode/compress/write
    workers consume whatever chunks have landed.  The caveat: deferred
    transfer relies on JAX immutability, so states updated with donated
    buffers (``donate_argnums``) must materialize every leaf before the
    donating step runs — set ``CheckpointPlan.eager_snapshot=True`` (the
    manager then constructs this snapshot with ``defer_device=False``,
    trading the pipelined blocking win for donation safety).  The in-repo
    trainer does not donate, so the knob defaults off.

  * ``LeafSource`` is the uniform interface the parallel writers consume:
    leaf names/specs are known immediately (shard planning needs no bytes),
    ``get(name)`` blocks until that leaf's bytes are host-resident.  A
    plain pytree wraps into ``PlainLeafSource`` so every existing call
    site keeps working.

  * Two pools, deliberately: transfer tasks (D2H) run on ``transfer_pool``
    and compression/write tasks on ``io_pool``.  IO tasks wait on transfer
    futures, never the reverse, so sharing one pool could not deadlock —
    but separating them keeps a slow zlib encode from starving the
    device->host stream that feeds it.
"""
from __future__ import annotations

import functools
import os
import threading
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Optional

import jax
import numpy as np

# GROUP comes from the numpy-only oracle module so importing the pipeline
# never pays for a pallas import (the jit'd ops load lazily, per call site)
from repro.kernels.ckpt_delta.ref import GROUP
from repro.utils.timing import current, span
from repro.utils.trees import tree_flatten_with_names

DEFAULT_CHUNK_BYTES = 4 << 20     # D2H granularity: first chunk = blocking

_pool_lock = threading.Lock()
_transfer_pool: Optional[ThreadPoolExecutor] = None
_io_pool: Optional[ThreadPoolExecutor] = None


def transfer_pool() -> ThreadPoolExecutor:
    """Background device->host chunk transfers (small: D2H is one link)."""
    global _transfer_pool
    with _pool_lock:
        if _transfer_pool is None:
            _transfer_pool = ThreadPoolExecutor(
                max_workers=2, thread_name_prefix="ckpt-d2h")
        return _transfer_pool


def io_pool() -> ThreadPoolExecutor:
    """Shared encode/compress/write workers for all checkpoint stores."""
    global _io_pool
    with _pool_lock:
        if _io_pool is None:
            _io_pool = ThreadPoolExecutor(
                max_workers=min(8, max(2, (os.cpu_count() or 2))),
                thread_name_prefix="ckpt-io")
        return _io_pool


class LeafSource:
    """Leaf-level access to a checkpoint state for the pipelined writers.

    ``names``/``spec`` are available immediately so shard assignment and
    manifests never wait on bytes; ``get(name)`` blocks until that leaf is
    host-resident.
    """

    names: list
    treedef: Any

    def spec(self, name: str) -> tuple[tuple, np.dtype]:
        raise NotImplementedError

    def nbytes(self, name: str) -> int:
        shape, dtype = self.spec(name)
        return int(np.prod(shape, dtype=np.int64)) * dtype.itemsize if shape \
            else dtype.itemsize

    def get(self, name: str) -> np.ndarray:
        raise NotImplementedError

    def bytes_on_link(self) -> int:
        """Bytes this snapshot moves across the device->host link
        (pre-compression, post-encode).  Raw sources move every leaf's raw
        bytes; ``DeltaLeafSource`` overrides with the encoded-payload
        accounting — the quantity ``SaveReport.bytes_on_link`` reports and
        the cost model prices, distinct from the post-compression bytes
        that hit the disk."""
        return sum(self.nbytes(n) for n in self.names)

    def wait(self) -> None:
        """Block until every leaf is host-resident."""

    def as_pytree(self) -> Any:
        self.wait()
        return jax.tree_util.tree_unflatten(
            self.treedef, [self.get(n) for n in self.names])


class PlainLeafSource(LeafSource):
    """A fully host-resident pytree (no copy — leaves may alias the
    caller's arrays; use ``ChunkedHostSnapshot`` when the snapshot must
    survive in-place mutation)."""

    def __init__(self, state: Any):
        named = tree_flatten_with_names(state)
        self.treedef = jax.tree_util.tree_structure(state)
        self.names = [n for n, _ in named]
        self._leaves = {n: np.asarray(l) for n, l in named}

    def spec(self, name: str) -> tuple[tuple, np.dtype]:
        leaf = self._leaves[name]
        return tuple(leaf.shape), leaf.dtype

    def get(self, name: str) -> np.ndarray:
        return self._leaves[name]


class ChunkedHostSnapshot(LeafSource):
    """Point-in-time host snapshot with chunked, overlapped D2H transfer.

    Blocking work (done in ``__init__``): deep-copy of every mutable host
    leaf + synchronous materialization of the first device chunk (the
    device sync).  Everything else lands on ``transfer_pool`` and is pulled
    by ``get``/``wait``.
    """

    def __init__(self, state: Any, chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                 defer_device: bool = True):
        named = tree_flatten_with_names(state)
        self.treedef = jax.tree_util.tree_structure(state)
        self.names = [n for n, _ in named]
        self._spec: dict[str, tuple[tuple, np.dtype]] = {}
        self._leaves: dict[str, np.ndarray] = {}
        self._future_of: dict[str, Future] = {}

        deferred: list[tuple[str, Any]] = []
        for name, leaf in named:
            if defer_device and isinstance(leaf, jax.Array):
                # immutable: a reference is as good as a copy until the
                # transfer worker reads it
                self._spec[name] = (tuple(leaf.shape), np.dtype(leaf.dtype))
                deferred.append((name, leaf))
            else:
                # mutable host memory (or cheap scalar): copy NOW — the
                # caller may mutate it the moment save() returns
                arr = np.array(leaf, copy=True)
                self._spec[name] = (tuple(arr.shape), arr.dtype)
                self._leaves[name] = arr

        # byte-bounded chunks over the deferred device leaves
        chunks: list[list[tuple[str, Any]]] = []
        cur, cur_bytes = [], 0
        for name, leaf in deferred:
            cur.append((name, leaf))
            cur_bytes += self.nbytes(name)
            if cur_bytes >= chunk_bytes:
                chunks.append(cur)
                cur, cur_bytes = [], 0
        if cur:
            chunks.append(cur)

        if chunks:      # first chunk synchronously: the device sync point
            self._leaves.update(self._materialize(chunks[0]))
        pool, parent = transfer_pool(), current()
        for chunk in chunks[1:]:
            fut = pool.submit(self._materialize, chunk, parent)
            for name, _ in chunk:
                self._future_of[name] = fut

    def _materialize(self, chunk: list,
                     parent: Optional[int] = None) -> dict[str, np.ndarray]:
        # np.asarray on a jax.Array is the D2H copy (on the CPU backend it
        # may alias the immutable buffer, which is equally safe)
        with span("khaos.ckpt.d2h", parent=parent,
                  bytes=sum(self.nbytes(name) for name, _ in chunk)):
            return {name: np.asarray(leaf) for name, leaf in chunk}

    def spec(self, name: str) -> tuple[tuple, np.dtype]:
        return self._spec[name]

    def get(self, name: str) -> np.ndarray:
        fut = self._future_of.get(name)
        if fut is not None:
            return fut.result()[name]
        return self._leaves[name]

    def wait(self) -> None:
        for fut in self._future_of.values():
            fut.result()


@dataclass(frozen=True)
class FlatEntry:
    """One leaf's extent inside the packed mega-buffer (element units)."""

    name: str
    offset: int          # GROUP-aligned start
    size: int            # true (unpadded) element count
    shape: tuple

    @property
    def padded(self) -> int:
        return -(-self.size // GROUP) * GROUP


class FlatLayout:
    """Where each f32 leaf lives inside the packed mega-buffer.

    Every leaf is zero-padded to a whole number of GROUP(=1024)-element
    groups, so (a) offsets are GROUP-aligned and every group belongs to
    exactly ONE leaf — the kernel's per-group change statistics reduce
    exactly to per-leaf counts via ``group_leaf``, (b) int8 scale groups
    never straddle leaves, making any flat payload extent bit-identical
    to the per-leaf encoder's output, and (c) the decoder can slice any
    leaf back out by ``(offset, size, shape)``.  ``to_manifest()`` is the
    serialized form the delta manifest's ``"flat"`` section records.
    """

    def __init__(self, named_shapes: list):
        self.entries: list[FlatEntry] = []
        off = 0
        for name, shape in named_shapes:
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            entry = FlatEntry(name, off, size, tuple(shape))
            self.entries.append(entry)
            off += entry.padded
        self.total = off
        self.by_name = {e.name: e for e in self.entries}
        self.names = [e.name for e in self.entries]
        group_leaf = np.zeros(self.total // GROUP, np.int32)
        for i, entry in enumerate(self.entries):
            group_leaf[entry.offset // GROUP:
                       (entry.offset + entry.padded) // GROUP] = i
        self.group_leaf = group_leaf
        self._group_leaf_dev: Optional[jax.Array] = None

    def group_leaf_device(self) -> jax.Array:
        """The group->leaf index map, uploaded once and cached (the fused
        encoders scatter-add per-group stats over it)."""
        if self._group_leaf_dev is None:
            self._group_leaf_dev = jax.numpy.asarray(self.group_leaf)
        return self._group_leaf_dev

    def to_manifest(self) -> list:
        return [[e.name, e.offset, e.size, list(e.shape)]
                for e in self.entries]


def _spec(x: jax.Array) -> jax.ShapeDtypeStruct:
    """``x``'s shape and dtype, and its placement when it is committed
    (an uncommitted array may follow the other operands)."""
    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                sharding=x.sharding if x.committed else None)


def _packable(state: Any) -> list:
    """The (name, leaf) pairs the device plane packs: non-empty f32 device
    leaves, in tree order."""
    return [(name, leaf) for name, leaf in tree_flatten_with_names(state)
            if isinstance(leaf, jax.Array) and leaf.size > 0
            and np.dtype(leaf.dtype) == np.float32]


@functools.lru_cache(maxsize=8)
def _compiled_pack(specs: tuple):
    from repro.kernels.ckpt_delta.ops import pack_flat
    return pack_flat.lower(list(specs)).compile()


@functools.lru_cache(maxsize=8)
def _compiled_encoder(codec: str, new: jax.ShapeDtypeStruct,
                      base: jax.ShapeDtypeStruct, num_leaves: int,
                      interpret: bool):
    from repro.kernels.ckpt_delta.ops import (flat_int8_encode,
                                              flat_lossless_encode)
    encode = flat_lossless_encode if codec == "lossless" else flat_int8_encode
    group_leaf = jax.ShapeDtypeStruct((new.shape[0],), np.int32)
    return encode.lower(new, base, group_leaf, num_leaves=num_leaves,
                        interpret=interpret).compile()


def pack(leaves: list) -> jax.Array:
    """``pack_flat`` of ``leaves``, through the executable compiled for
    their shapes and placements."""
    return _compiled_pack(tuple(_spec(x) for x in leaves))(list(leaves))


def prepare_device_encode(state: Any, codec: str) -> None:
    """Compile ahead of time the pack and the flat ``codec`` encoder that
    a device-encode trigger runs on ``state``'s f32 leaves.  Compiling
    from shapes allocates nothing on the device."""
    from repro.kernels.ckpt_delta.ops import default_interpret
    leaves = [leaf for _, leaf in _packable(state)]
    if not leaves:
        return
    specs = tuple(_spec(x) for x in leaves)
    packer = _compiled_pack(specs)
    # the packed buffer is committed exactly when a packed leaf is
    committed = any(spec.sharding is not None for spec in specs)
    flat = jax.ShapeDtypeStruct(
        packer.out_info.shape, packer.out_info.dtype,
        sharding=packer.output_shardings if committed else None)
    _compiled_encoder(codec, flat, flat, len(leaves), default_interpret())


class DeviceDeltaBase:
    """The delta base held device-resident across triggers — per-leaf refs
    (for the fallback path and shape checks) plus the PACKED flat
    mega-buffer the fused encoder diffs against.

    Because ``jax.Array``s are immutable, holding references to the last
    full snapshot's device leaves is free — no extra HBM beyond delaying
    the old buffers' release — and gives the on-device encoder a base to
    diff against without any host round trip.  Mutable host leaves are
    deep-copied eagerly (the same aliasing contract as
    ``ChunkedHostSnapshot``).  The f32 subtree is additionally packed into
    ``flat`` under ``layout`` by one ``pack_flat`` dispatch — paid once
    per refresh and amortized over every delta trigger until the next.
    ``CheckpointManager`` refreshes this on every full trigger/savepoint
    and carries it across plan-switch rebuilds (``adopt_runtime_state``).
    """

    def __init__(self, state: Any):
        self.leaves: dict[str, Any] = {
            name: leaf if isinstance(leaf, jax.Array)     # immutable: ref
            else np.array(leaf, copy=True)                # == copy
            for name, leaf in tree_flatten_with_names(state)}
        packable = _packable(state)
        self.layout: Optional[FlatLayout] = None
        self.flat: Optional[jax.Array] = None
        if packable:
            self.layout = FlatLayout(
                [(name, tuple(leaf.shape)) for name, leaf in packable])
            self.flat = pack([leaf for _, leaf in packable])

    def flat_subset(self, names: list) -> tuple[FlatLayout, jax.Array]:
        """The packed base restricted to ``names`` (in that order).  The
        common case — the new state's packable subtree matches the base's
        exactly — returns the resident buffer as-is; after a drift
        (leaves removed or reordered) the surviving GROUP-aligned extents
        are sliced out and re-concatenated in one dispatch."""
        assert self.layout is not None and self.flat is not None
        if names == self.layout.names:
            return self.layout, self.flat
        sub = FlatLayout([(n, self.layout.by_name[n].shape) for n in names])
        parts = [self.flat[e.offset // GROUP:(e.offset + e.padded) // GROUP]
                 for e in (self.layout.by_name[n] for n in names)]
        return sub, jax.numpy.concatenate(parts)


class DeltaLeafSource(LeafSource):
    """Delta-encode on device with ONE fused kernel over the packed flat
    buffer, then stream only the ENCODED payload D2H in chunks.

    ``__init__`` does the whole blocking dance: pack the new state's f32
    subtree (one ``pack_flat`` dispatch), run one fused
    ``flat_lossless_encode``/``flat_int8_encode`` against the resident
    ``DeviceDeltaBase.flat``, pull the per-LEAF change statistics (that
    tiny stats read is the device sync — the encode is complete), then
    materialize the FIRST payload chunk synchronously — the same
    first-chunk-sync ``blocking_s`` contract as ``ChunkedHostSnapshot`` —
    and queue the remaining byte-bounded chunks on ``transfer_pool``.

    Consumed two ways:

      * ``layout`` + ``flat_payload()`` + ``zero_names`` — the flat
        protocol ``incremental.write_delta`` detects (via
        ``getattr(src, "layout", None)``): the packed extents' manifest
        rows, the host-resident payload arrays ("d"/"r" lossless,
        "q"/"s" int8; ``"zero"`` marks a residual plane whose D2H was
        skipped), and the leaves whose fused change count was 0 (the
        skip-zero manifest markers).  Leaves OUTSIDE the packed subtree
        (non-f32, host-resident, zero-size, or base-shape drift) are
        absent from ``layout`` and take the per-leaf host-encode path.
      * ``get(name)`` — the raw leaf, materialized lazily (memory-level
        parking and the rare delta-upgraded-to-full self-heal write);
        device refs are immutable so the late D2H is safe.

    Lossless payloads are delta (f32) + XOR residual (u32) over the whole
    flat buffer — the residual is all-zero for any element within 2x of
    its base (Sterbenz), so when the fused per-leaf nonzero counts sum to
    0 the residual plane's D2H is skipped entirely and the decoder
    reconstructs zeros.  int8 payloads are q (1 B/elem) + per-1024 f32
    scales — ~0.26x state bytes on the link.  When EVERY packed leaf is
    unchanged nothing crosses the link at all.
    """

    placement = "device"

    def __init__(self, state: Any, base: DeviceDeltaBase,
                 codec: str = "lossless",
                 chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                 interpret: Optional[bool] = None):
        assert codec in ("lossless", "int8"), codec
        from repro.kernels.ckpt_delta.ops import default_interpret
        self.codec = codec
        self.interpret = default_interpret() if interpret is None \
            else interpret
        named = tree_flatten_with_names(state)
        self.treedef = jax.tree_util.tree_structure(state)
        self.names = [n for n, _ in named]
        self._spec: dict[str, tuple[tuple, np.dtype]] = {}
        self._raw: dict[str, Any] = {}
        self._payload: dict[str, Any] = {}       # suffix -> host np / "zero"
        self._chunk_futs: list[Future] = []
        self._link_lock = threading.Lock()
        self._link_bytes = 0
        self.layout: Optional[FlatLayout] = None
        self.zero_names: tuple = ()

        packed: list[tuple[str, Any]] = []
        for name, leaf in named:
            if isinstance(leaf, jax.Array):
                self._spec[name] = (tuple(leaf.shape), np.dtype(leaf.dtype))
                self._raw[name] = leaf
                entry = None if base.layout is None \
                    else base.layout.by_name.get(name)
                if (entry is not None
                        and np.dtype(leaf.dtype) == np.float32
                        and entry.shape == tuple(leaf.shape)):
                    packed.append((name, leaf))
                # else: fallback leaf — per-leaf host encode; its raw D2H
                # is accounted when write_delta actually pulls it in get()
            else:
                arr = np.array(leaf, copy=True)   # mutable host leaf
                self._spec[name] = (tuple(arr.shape), arr.dtype)
                self._raw[name] = arr
                self._account(arr.nbytes)

        if not packed:
            return

        layout, base_flat = base.flat_subset([n for n, _ in packed])
        self.layout = layout
        new_flat = pack([leaf for _, leaf in packed])
        encode = _compiled_encoder(codec, _spec(new_flat), _spec(base_flat),
                                   len(packed), self.interpret)
        group_leaf = layout.group_leaf_device()
        if codec == "lossless":
            d, r, leaf_changed, leaf_rnnz = encode(new_flat, base_flat,
                                                   group_leaf)
            changed = np.asarray(leaf_changed)    # stats pull = device sync
            arrays: list[tuple[str, Any]] = []
            if changed.any():
                arrays.append(("d", d))
                if int(np.asarray(leaf_rnnz).sum()):
                    arrays.append(("r", r))
                else:           # residual known all-zero: skip its D2H —
                    self._payload["r"] = "zero"   # decoder reconstructs
        else:
            q, s, leaf_changed = encode(new_flat, base_flat, group_leaf)
            changed = np.asarray(leaf_changed)    # stats pull = device sync
            arrays = [("q", q), ("s", s)] if changed.any() else []
        self.zero_names = tuple(
            entry.name for entry, c in zip(layout.entries, changed) if not c)
        self._start_transfers(arrays, chunk_bytes)

    def _start_transfers(self, arrays: list, chunk_bytes: int) -> None:
        """Chunk the encoded payload arrays and stream them D2H: first
        chunk synchronously (the blocking cost), the rest on the pool."""
        tasks: list[tuple] = []
        for sfx, dev in arrays:
            # rows of the (ng, GROUP) planes, or elements of the 1-D scales
            host = np.empty(dev.shape, np.dtype(dev.dtype))
            self._payload[sfx] = host.reshape(-1)      # a view: filled below
            row_bytes = host[:1].nbytes
            per = max(1, chunk_bytes // row_bytes)
            for a in range(0, host.shape[0], per):
                tasks.append((host, dev, a, min(host.shape[0], a + per)))
        if not tasks:
            return
        self._pull_chunk(*tasks[0])
        pool, parent = transfer_pool(), current()
        self._chunk_futs = [pool.submit(self._pull_chunk, *task, parent)
                            for task in tasks[1:]]

    def _pull_chunk(self, host: np.ndarray, dev: Any, a: int, b: int,
                    parent: Optional[int] = None) -> None:
        with span("khaos.ckpt.d2h", parent=parent, bytes=host[a:b].nbytes):
            host[a:b] = np.asarray(dev[a:b])
        self._account(host[a:b].nbytes)

    def _account(self, nbytes: int) -> None:
        with self._link_lock:
            self._link_bytes += int(nbytes)

    # -- flat protocol for incremental.write_delta ----------------------
    def flat_payload(self) -> dict:
        """suffix -> host payload array ("d"/"r" lossless, "q"/"s" int8)
        or the ``"zero"`` marker for a skipped all-zero residual plane;
        empty when every packed leaf was unchanged.  Blocks until every
        chunk has landed."""
        self.wait()
        return dict(self._payload)

    # -- LeafSource interface -------------------------------------------
    def spec(self, name: str) -> tuple[tuple, np.dtype]:
        return self._spec[name]

    def get(self, name: str) -> np.ndarray:
        leaf = self._raw[name]
        if isinstance(leaf, np.ndarray):
            return leaf
        arr = np.asarray(leaf)
        with self._link_lock:
            cur = self._raw[name]
            if isinstance(cur, np.ndarray):     # another worker won the race
                return cur
            self._raw[name] = arr
            # a raw pull IS link traffic (remote/self-heal full writes,
            # memory-level restores, and per-leaf fallback encodes pull raw
            # leaves from a delta source) — count it so bytes_on_link never
            # under-reports
            self._link_bytes += arr.nbytes
        return arr

    def wait(self) -> None:
        for fut in self._chunk_futs:
            fut.result()

    def bytes_on_link(self) -> int:
        self.wait()
        with self._link_lock:
            return self._link_bytes


def as_leaf_source(state: Any) -> LeafSource:
    """Adapt ``state`` (pytree or LeafSource) for the pipelined writers."""
    if isinstance(state, LeafSource):
        return state
    return PlainLeafSource(state)
