"""Core data structures for SSumM: graphs, summary state, pair tables.

All structures are fixed-shape pytrees so every phase of the algorithm is
jit-compilable. ``V``/``E`` are static; supernode ids live in ``[0, V)`` and
dead ids are marked by ``size == 0``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def _pytree(cls):
    """Register a dataclass as a pytree (all fields are children)."""
    fields = [f.name for f in dataclasses.fields(cls)]

    def flatten(obj):
        return tuple(getattr(obj, f) for f in fields), None

    def unflatten(_, children):
        return cls(*children)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


@_pytree
@dataclasses.dataclass
class Graph:
    """Canonical undirected simple graph: ``src < dst``, no self-loops, unique."""

    src: jax.Array  # int32[E]
    dst: jax.Array  # int32[E]

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])

    def num_nodes_static(self) -> int:
        raise NotImplementedError("use Graph holders with explicit V (see make_graph)")


@_pytree
@dataclasses.dataclass
class SummaryState:
    """Functional state of the summarization search.

    ``node2super[v]`` maps every subnode to its current supernode id.
    ``size[a]`` is the number of subnodes in supernode ``a`` (0 = dead id).
    """

    node2super: jax.Array  # int32[V]
    size: jax.Array  # int32[V]
    rng: jax.Array  # PRNG key
    t: jax.Array  # int32 scalar, 1-based iteration counter

    @property
    def num_supernodes(self) -> jax.Array:
        return jnp.sum(self.size > 0).astype(jnp.int32)


@_pytree
@dataclasses.dataclass
class PairTable:
    """Aggregated supernode-pair table derived from the edge list.

    Fixed capacity ``E`` rows (a partition can induce at most ``E`` distinct
    supernode pairs with nonzero subedge count). ``valid`` masks live rows.
    Self-pairs are rows with ``lo == hi``.

    As ``costs.build_pair_table`` returns it, the table is the edge list in
    sorted ``(lo, hi)`` order: the valid rows are not a prefix but the first
    entry of each pair's run, in ascending ``(lo, hi)`` order, each with the
    run's length as ``cnt``. The other rows repeat a real pair's ids and
    carry ``cnt`` 0, so every reader masks with ``valid`` or ``cnt``.
    """

    lo: jax.Array  # int32[E]
    hi: jax.Array  # int32[E]
    cnt: jax.Array  # float32[E]  |E_AB| (exact integers in float32)
    valid: jax.Array  # bool[E]

    @property
    def capacity(self) -> int:
        return int(self.lo.shape[0])


@dataclasses.dataclass(frozen=True)
class SummaryConfig:
    """Hyper-parameters of the search (static; part of jit cache keys).

    Mirrors Sect. 3 of the paper; TPU-adaptation knobs are documented in
    DESIGN.md §3/§4.
    """

    T: int = 20  # outer iterations (paper default, Fig. 8)
    k_frac: float | None = None  # target size as a fraction of Size(G)
    k_bits: float | None = None  # absolute target size in bits
    group_size: int = 32  # C_max — candidate-set cap (paper: 500)
    max_neighbors: int = 64  # D_max — per-supernode scored-neighbor cap
    union_size: int = 128  # U_max — per-group union-neighbor columns
    cbar_mode: str = "tight"  # "paper": 2log2|V|+log2|E|; "tight": footnote 3
    re_guard: int = 1  # 0 = off; p in {1,2}: never keep superedges that raise RE_p
    error_p: int = 1  # p for the final sparsification deltas (footnote 4)
    ensure_budget: bool = True  # extra θ=0 iterations if membership term > k
    max_extra_iters: int = 40
    # merge-gain scoring backend, resolved through the kernel-dispatch
    # registry (repro.kernels.ops): "ref" (jitted jnp oracle — the XLA path
    # a CPU host runs), "pallas-interpret" (kernel body in Python, the CI
    # validation lane), or "pallas" (compiled, real accelerators). None
    # defers to $SSUMM_KERNEL, then "ref" — an explicit value here always
    # beats the environment.
    kernel_backend: str | None = None
    # R — merge rounds per device dispatch of the engine's chunked driver
    # (lax.while_loop; scalar metrics reach the host only on chunk
    # boundaries). 1 recovers the historical sync-every-round driver.
    driver_chunk: int = 8
    seed: int = 0

    def target_bits(self, size_g: float) -> float:
        if self.k_bits is not None:
            return float(self.k_bits)
        if self.k_frac is not None:
            return float(self.k_frac) * float(size_g)
        return 0.3 * float(size_g)


@dataclasses.dataclass
class SummaryResult:
    """Final output: the summary graph Ḡ = (S, P, ω) plus evaluation stats."""

    node2super: np.ndarray  # int32[V]
    super_size: np.ndarray  # int32[V]
    edge_lo: np.ndarray  # int32[P] superedge endpoints (supernode ids)
    edge_hi: np.ndarray  # int32[P]
    edge_w: np.ndarray  # int64[P] ω
    num_supernodes: int
    num_superedges: int
    size_bits: float  # Eq. (4)
    input_size_bits: float  # Eq. (3)
    re1: float  # normalized ℓ1 reconstruction error
    re2: float  # normalized ℓ2 reconstruction error
    mdl_cost: float  # Eq. (14)
    iterations_run: int
    history: list[dict[str, Any]] = dataclasses.field(default_factory=list)
    # fault-tolerance / observability bookkeeping (engine pass-through;
    # DESIGN.md §13) — empty/zero when the run was plain and uninterrupted
    chunk_wall_s: list = dataclasses.field(default_factory=list)
    straggler_events: list = dataclasses.field(default_factory=list)
    resumed_from: int | None = None
    checkpoint_saves: int = 0
    checkpoint_snapshot_wall_s: float = 0.0


@functools.partial(jax.profiler.annotate_function, name="ssumm.make_graph")
def make_graph(src, dst, num_nodes: int) -> tuple[Graph, int]:
    """Canonicalize an edge list: undirected, dedup, no self-loops, src<dst,
    and copy it to the device (host span ``ssumm.make_graph``)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    lo = np.minimum(src, dst)
    hi = np.maximum(src, dst)
    keep = lo != hi
    lo, hi = lo[keep], hi[keep]
    key = lo * int(num_nodes) + hi
    _, idx = np.unique(key, return_index=True)
    lo, hi = lo[idx], hi[idx]
    g = Graph(src=jnp.asarray(lo, jnp.int32), dst=jnp.asarray(hi, jnp.int32))
    return g, int(num_nodes)


def init_state(num_nodes: int, seed: int = 0) -> SummaryState:
    """Ḡ := G (Alg. 1 lines 1–2): every subnode is its own supernode."""
    return SummaryState(
        node2super=jnp.arange(num_nodes, dtype=jnp.int32),
        size=jnp.ones((num_nodes,), dtype=jnp.int32),
        rng=jax.random.PRNGKey(seed),
        t=jnp.asarray(1, jnp.int32),
    )
