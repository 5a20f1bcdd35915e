"""The edge-sharding rule of distributed summarization
(:mod:`repro.dist.sharding`): edges over every mesh axis, partition state
replicated, and the supernode ownership hash the edge-sharded step and the
query tiers route with."""

from repro.dist.sharding import (
    OWNER_HASH_MULT,
    MeshRules,
    make_mesh,
    make_rules,
    owner_hash_np,
)

__all__ = [
    "OWNER_HASH_MULT",
    "MeshRules",
    "make_mesh",
    "make_rules",
    "owner_hash_np",
]
