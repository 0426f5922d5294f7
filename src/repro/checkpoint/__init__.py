from repro.checkpoint.store import CheckpointStore, CheckpointMeta
from repro.checkpoint.async_ckpt import AsyncCheckpointer, BackgroundCommitter
from repro.checkpoint.incremental import IncrementalCheckpointer
from repro.checkpoint.multilevel import MultiLevelCheckpointer
from repro.checkpoint.pipeline import (ChunkedHostSnapshot, DeltaLeafSource,
                                       DeviceDeltaBase, FlatLayout,
                                       LeafSource, PlainLeafSource,
                                       as_leaf_source)
from repro.checkpoint.policy import CheckpointPolicy
from repro.checkpoint.manager import (CheckpointManager, Checkpointer,
                                      RestoreReport, SaveReport)
from repro.checkpoint.replication import (PeerReplicatedStore, ReplicaStats,
                                          ReplicationError, retry_with_backoff,
                                          ring_peers)
from repro.config import CheckpointPlan

__all__ = [
    "CheckpointStore", "CheckpointMeta", "AsyncCheckpointer",
    "BackgroundCommitter", "IncrementalCheckpointer",
    "MultiLevelCheckpointer", "CheckpointPolicy", "CheckpointManager",
    "Checkpointer", "CheckpointPlan", "SaveReport", "RestoreReport",
    "ChunkedHostSnapshot", "DeltaLeafSource", "DeviceDeltaBase",
    "FlatLayout", "LeafSource", "PlainLeafSource", "as_leaf_source",
    "PeerReplicatedStore", "ReplicaStats", "ReplicationError",
    "retry_with_backoff", "ring_peers",
]
