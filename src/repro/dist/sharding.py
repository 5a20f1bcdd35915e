"""Sharding rule tables: logical axis names → mesh axes, per launch mode.

Every path that places data on a mesh — the lowering/dry-run cells, the
train and serve drivers, and the distributed SSumM step — resolves its
shardings through one :class:`MeshRules` table built by
:func:`make_rules(mesh, mode)`. Logical names are the vocabulary the model
``axes()`` trees and ``rules.constrain`` call sites already speak:

    batch seq kvseq embed act_embed attn_embed heads kv_heads ff vocab
    experts                                  (LM stack)
    edges                                    (edge-sharded summarization)

Modes:
  * ``train``     — DP over (pod, data), TP over model, FSDP: the ``embed``
    parameter dimension is additionally sharded over the DP axes;
  * ``serve``     — TP over model plus sequence parallelism (``seq``) and
    flash-decoding cache splits (``kvseq``) on the model axis;
  * ``summarize`` — edges sharded over *every* mesh axis, partition state
    replicated (DESIGN.md §7), plus the supernode ownership hash used by
    the pair-routing all-to-all;
  * ``eval``      — offline batch inference: the batch dimension is
    sharded over *every* mesh axis (throughput, not latency, is the
    objective) and parameters stay replicated — no TP collectives in the
    step, so independent shards stream through with zero cross-device
    traffic.

Rule application is shape-aware: a mesh axis is dropped for a given array
dimension when it does not divide the dimension or is already taken by an
earlier dimension of the same spec — smoke-sized configs lower on any mesh
without per-call special-casing.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

# Tensor-parallel parameter/activation dimensions: sharded over "model" in
# every LM mode.
_TP_AXES = ("ff", "heads", "kv_heads", "vocab", "experts", "attn_embed")

# Logical names every mode's table defines (the full vocabulary above).
_LOGICAL = _TP_AXES + (
    "batch", "seq", "kvseq", "embed", "act_embed", "edges",
)

# Knuth multiplicative constant for the re-drawable supernode ownership
# hash — defined once here so the distributed step and any tooling that
# predicts record placement agree on the routing.
OWNER_HASH_MULT = 2654435761

MODES = ("train", "serve", "summarize", "eval")


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


def _dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _tp_axis(mesh):
    return "model" if "model" in mesh.axis_names else None


@dataclasses.dataclass(frozen=True)
class MeshRules:
    """A resolved logical-axis → mesh-axis table bound to one mesh."""

    mesh: Any
    mode: str
    table: Mapping[str, Any]  # logical name -> mesh axis | tuple | None

    # ------------------------------------------------------------ topology
    @property
    def axis_names(self) -> tuple:
        return tuple(self.mesh.axis_names)

    @property
    def n_devices(self) -> int:
        return int(self.mesh.size)

    @property
    def dp_axes(self) -> tuple:
        return _dp_axes(self.mesh)

    # ------------------------------------------------------- spec assembly
    def mesh_axes(self, logical) -> tuple:
        """The (possibly multi-axis) mesh assignment of one logical name."""
        if logical is None:
            return ()
        if logical not in self.table:  # typos must not silently replicate
            raise KeyError(
                f"unknown logical axis {logical!r}; known: {sorted(self.table)}"
            )
        assign = self.table[logical]
        if assign is None:
            return ()
        return (assign,) if isinstance(assign, str) else tuple(assign)

    def spec(self, logical_axes, shape=None) -> P:
        """PartitionSpec for a tuple of logical names.

        ``shape`` (when given) enables the divisibility guard; an axis
        already consumed by an earlier dimension is never reused.
        """
        used: set = set()
        entries = []
        for i, name in enumerate(logical_axes):
            kept = []
            prod = 1
            dim = None if shape is None else shape[i]
            for ax in self.mesh_axes(name):
                if ax in used or ax not in self.mesh.shape:
                    continue
                size = int(self.mesh.shape[ax])
                if dim is not None and dim % (prod * size) != 0:
                    continue
                kept.append(ax)
                used.add(ax)
                prod *= size
            if not kept:
                entries.append(None)
            elif len(kept) == 1:
                entries.append(kept[0])
            else:
                entries.append(tuple(kept))
        return P(*entries)

    def sharding(self, logical_axes, shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical_axes, shape))

    def constrain(self, x, *logical_axes):
        """``with_sharding_constraint`` under this table (shape-guarded)."""
        return jax.lax.with_sharding_constraint(
            x, self.sharding(logical_axes, x.shape)
        )

    # ----------------------------------------- distributed summarization
    @property
    def edge_spec(self) -> P:
        """Edge shards: dimension 0 split over every mesh axis."""
        return self.spec(("edges",))

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


def _mode_table(mesh, mode: str) -> dict:
    dp = _dp_axes(mesh) or None
    tp = _tp_axis(mesh)
    table: dict = {name: None for name in _LOGICAL}
    if mode == "summarize":
        table["edges"] = tuple(mesh.axis_names)
        table["batch"] = dp
        return table
    if mode == "eval":
        # offline batch: every device is a data-parallel lane; weights
        # replicated, so the only sharded dimension is the batch.
        table["batch"] = tuple(mesh.axis_names)
        return table
    table.update({name: tp for name in _TP_AXES})
    table["batch"] = dp
    if mode == "train":
        # FSDP: parameters additionally sharded over the DP axes along the
        # embed dimension (gathered on the fly by GSPMD).
        table["embed"] = dp
    elif mode == "serve":
        # sequence parallelism for prefill activations, flash-decoding
        # splits for the KV cache — both on the TP axis.
        table["seq"] = tp
        table["kvseq"] = tp
    return table


def _validate_override(mesh, key: str, val) -> None:
    """An override must name real axes of *this* mesh (or None).

    Without this check a typo'd axis (``seq=modell``) silently replicates
    the dimension — ``MeshRules.spec`` drops unknown axes by design for
    shape-guarding, which is exactly wrong for user-supplied overrides.
    """
    if val is None:
        return
    if isinstance(val, str):
        axes = (val,)
    elif isinstance(val, (tuple, list)):
        axes = tuple(val)
    else:
        raise ValueError(
            f"override {key!r}={val!r}: expected a mesh axis name, a "
            f"tuple of names, or None; got {type(val).__name__}"
        )
    mesh_axes = tuple(mesh.axis_names)
    for ax in axes:
        if not isinstance(ax, str) or ax not in mesh_axes:
            raise ValueError(
                f"override {key!r}={val!r}: {ax!r} is not an axis of this "
                f"mesh; mesh axes: {mesh_axes}"
            )
    if len(set(axes)) != len(axes):
        raise ValueError(
            f"override {key!r}={val!r} names a mesh axis more than once"
        )


def make_rules(mesh, mode: str, *, overrides: Mapping[str, Any] | None = None,
               ) -> MeshRules:
    """Build the rule table for ``mesh`` in ``mode``.

    ``overrides`` remaps individual logical names (value: mesh axis name,
    tuple of names, or None to replicate) — the dry-run's perf-iteration
    knobs (``seq=model``, ``batch=data+model``, …) come through here. Keys
    must be known logical names and values must name axes of ``mesh``;
    both are validated eagerly with a KeyError/ValueError rather than
    silently replicating the dimension.
    """
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")
    table = _mode_table(mesh, mode)
    for key, val in (overrides or {}).items():
        if key not in table:
            raise KeyError(
                f"unknown logical axis {key!r}; known: {sorted(table)}"
            )
        _validate_override(mesh, key, val)
        table[key] = val
    return MeshRules(mesh=mesh, mode=mode, table=table)
