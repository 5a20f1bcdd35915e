"""The edge-sharding rule of distributed summarization.

Every path that places summarization data on a mesh — the distributed
SSumM step, the cache feed, and the owner-routed and partitioned query
tiers — resolves its shardings through one :class:`MeshRules` built by
:func:`make_rules(mesh)`: edges are sharded over *every* mesh axis,
partition state is replicated (DESIGN.md §7), and supernodes are routed by
a re-drawable ownership hash.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

# Knuth multiplicative constant for the re-drawable supernode ownership
# hash — defined once here so the distributed step and any tooling that
# predicts record placement agree on the routing.
OWNER_HASH_MULT = 2654435761


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` over every device, all axes auto-partitioned."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def owner_hash_np(ids, salt: int, n_devices: int) -> "np.ndarray":
    """Numpy twin of :meth:`MeshRules.owner` — same uint32 math, host side.

    The partitioned query tier builds its halo tables on the host before
    any device data exists; it must agree bit-for-bit with the device-side
    routing hash (tests/test_sharding_rules.py pins the equivalence).
    """
    ids = np.asarray(ids)
    with np.errstate(over="ignore"):
        x = (ids.astype(np.uint32) * np.uint32(OWNER_HASH_MULT)) ^ np.uint32(
            salt
        )
    x = (x >> np.uint32(16)) ^ x
    return (x % np.uint32(max(1, int(n_devices)))).astype(np.int32)


@dataclasses.dataclass(frozen=True)
class MeshRules:
    """The edge-sharding table bound to one mesh."""

    mesh: Any

    @property
    def axis_names(self) -> tuple:
        return tuple(self.mesh.axis_names)

    @property
    def n_devices(self) -> int:
        return int(self.mesh.size)

    @property
    def edge_spec(self) -> P:
        """Edge shards: dimension 0 split over every mesh axis."""
        axes = self.axis_names
        return P(axes[0] if len(axes) == 1 else axes)

    @property
    def replicated(self) -> P:
        return P()

    def owner(self, ids, salt):
        """Device owning supernode ``ids`` for this iteration's ``salt``.

        Cheap re-drawable multiplicative hash (mod device count): re-drawn
        every iteration so all supernode pairs are eventually co-owned —
        the distributed analogue of the paper's disjoint candidate sets.
        """
        x = (ids.astype(jnp.uint32) * jnp.uint32(OWNER_HASH_MULT)) ^ (
            salt.astype(jnp.uint32)
        )
        x = (x >> 16) ^ x
        return (x % jnp.uint32(self.n_devices)).astype(jnp.int32)


def make_rules(mesh) -> MeshRules:
    """The edge-sharding rules of ``mesh``."""
    return MeshRules(mesh=mesh)
