"""Index metadata operations: dynamic settings, open/close.

Reference: org/elasticsearch/cluster/metadata/ —
MetaDataUpdateSettingsService.java (dynamic vs static settings; static ones
need a closed index), MetaDataIndexStateService.java (open/close blocks).

The template-matching and alias logic live on Node (create_index /
update_aliases); this module covers the mutation paths that change a LIVE
index: replica count scaling (builds/drops replica IndexShards and
re-syncs them via peer recovery) and refresh cadence.
"""
from __future__ import annotations

from typing import Dict

from elasticsearch_tpu.utils.errors import ElasticsearchTpuException, IllegalArgumentException

# settings changeable on an open index (reference: IndexDynamicSettings)
DYNAMIC_SETTINGS = {
    "number_of_replicas",
    "refresh_interval",
    "blocks.read_only",
    "blocks.read",
    "blocks.write",
}
# whole dynamically-updatable families (reference: the slowlog thresholds
# are per-level dynamic settings — IndexDynamicSettingsModule registers
# index.search.slowlog.* / index.indexing.slowlog.*)
DYNAMIC_SETTING_PREFIXES = ("search.slowlog.", "indexing.slowlog.")


class IndexClosedException(ElasticsearchTpuException):
    status = 403
    error_type = "index_closed_exception"


def _flatten(settings: dict, prefix: str = "") -> Dict[str, object]:
    out: Dict[str, object] = {}
    for k, v in settings.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, f"{key}."))
        else:
            out[key] = v
    return out


#: public name for cross-layer consumers (reference: Settings.flatten —
#: ES accepts nested AND dotted settings bodies everywhere; the cluster
#: settings route uses this so `{"cluster": {"routing": ...}}` and
#: `"cluster.routing...."` land as the same dotted keys the allocator,
#: breakers, and serving services key their live-apply maps by)
flatten_settings = _flatten


def update_index_settings(svc, body: dict, node=None) -> dict:
    """PUT /{index}/_settings — dynamic settings only on an open index.

    Persistence happens HERE (given a node), not in transport handlers, so
    every entry point that changes settings also survives restarts."""
    flat = _flatten(body.get("settings", body))
    flat = {k[len("index."):] if k.startswith("index.") else k: v
            for k, v in flat.items()}
    for key in flat:
        if key not in DYNAMIC_SETTINGS \
                and not key.startswith(DYNAMIC_SETTING_PREFIXES):
            raise IllegalArgumentException(
                f"setting [index.{key}] is not dynamically updateable")
    if "number_of_replicas" in flat:
        _scale_replicas(svc, int(flat["number_of_replicas"]))
    idx = svc.settings.setdefault("index", {})
    for k, v in flat.items():
        idx[k] = v
    if node is not None:
        node._persist_index_meta(svc.name)
    return {"acknowledged": True}


def _scale_replicas(svc, target: int) -> None:
    """Grow or shrink every shard's replica set (reference: replica count is
    the canonical dynamic setting; new copies peer-recover from the
    primary)."""
    from elasticsearch_tpu.index.recovery import recover_peer
    from elasticsearch_tpu.index.shard import IndexShard

    if target < 0:
        raise IllegalArgumentException("number_of_replicas must be >= 0")
    for group in svc.groups:
        with group._lock:  # writes fan out under this same lock
            while len(group.replicas) > target:
                group.replicas.pop().close()
            while len(group.replicas) < target:
                replica = IndexShard(
                    svc.name, group.shard_id, svc.mappings, svc.analysis,
                    None, device=svc.shard_device(
                        group.shard_id, len(group.replicas) + 1))
                recover_peer(group.primary.engine, replica.engine)
                group.replicas.append(replica)
    svc.num_replicas = target


def close_index(node, name: str) -> dict:
    """POST /{index}/_close — index stays registered, ops are blocked."""
    svc = node.get_index(name)
    svc.closed = True
    meta = node.cluster_state.indices.get(name)
    if meta is not None:
        meta.state = "close"
    node.cluster_state.next_version()
    node._persist_index_meta(svc.name)
    return {"acknowledged": True}


def open_index(node, name: str) -> dict:
    svc = node.get_index(name)
    svc.closed = False
    meta = node.cluster_state.indices.get(name)
    if meta is not None:
        meta.state = "open"
    node.cluster_state.next_version()
    node._persist_index_meta(svc.name)
    return {"acknowledged": True}


class IndexBlockedException(ElasticsearchTpuException):
    status = 403
    error_type = "cluster_block_exception"


def _block(svc, key: str) -> bool:
    idx = svc.settings.get("index", svc.settings)
    v = idx.get(f"blocks.{key}", idx.get("blocks", {}).get(key)
                if isinstance(idx.get("blocks"), dict) else None)
    return v in (True, "true", "1", 1)


def check_open(svc, op: str = "write") -> None:
    """Guard for write/search paths (reference: ClusterBlocks check) —
    enforces both the open/close state and the blocks.* settings."""
    if getattr(svc, "closed", False):
        raise IndexClosedException(f"closed index [{svc.name}]")
    if op == "write" and (_block(svc, "write") or _block(svc, "read_only")):
        raise IndexBlockedException(
            f"index [{svc.name}] blocked: blocks.write/read_only")
    if op == "read" and _block(svc, "read"):
        raise IndexBlockedException(f"index [{svc.name}] blocked: blocks.read")
