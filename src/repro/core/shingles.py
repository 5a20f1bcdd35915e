"""Candidate generation (Sect. 3.2.2): min-hash shingles → candidate groups.

Paper: supernodes sharing a shingle are within 2 hops; oversized groups are
split recursively (≤10×) then randomly, capped at 500 supernodes.

TPU adaptation (DESIGN.md §3): the random bijection ``h`` is a sampled
permutation; ``f(A)`` is computed with two segment-min passes; grouping is
one sort by ``(dead, shingle, rand)`` followed by fixed-size chunking into
``[G, C]`` tiles. Chunk boundaries may mix adjacent shingles — such pairs
are simply scored low and rejected by θ(t), so correctness is unaffected.
Randomness is refreshed every iteration, which subsumes the paper's
recursive re-splitting.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import SummaryState


def node_shingles(
    src: jax.Array, dst: jax.Array, num_nodes: int, rng: jax.Array
) -> jax.Array:
    """Per-subnode ``min(h(u), min_{(u,v)∈E} h(v))`` for a fresh bijection h."""
    h = jax.random.permutation(rng, num_nodes).astype(jnp.int32)
    f = h  # include h(u) itself (closed neighborhood)
    f = f.at[src].min(h[dst])
    f = f.at[dst].min(h[src])
    return f


def supernode_shingles(
    src: jax.Array, dst: jax.Array, state: SummaryState, rng: jax.Array
) -> jax.Array:
    """``f(A) = min_{u∈A} node_shingle(u)`` via one more segment-min pass."""
    num_nodes = state.node2super.shape[0]
    nf = node_shingles(src, dst, num_nodes, rng)
    out = jnp.full((num_nodes,), num_nodes, dtype=jnp.int32)
    out = out.at[state.node2super].min(nf)
    return out  # dead ids keep the sentinel ``num_nodes``


def chunk_groups(
    shingle: jax.Array,
    size: jax.Array,
    rng: jax.Array,
    group_size: int,
) -> jax.Array:
    """Sort supernodes by (dead, shingle, random) and chunk into ``[G, C]``.

    Active supernodes sharing a shingle land in the same chunk; dead ids are
    pushed to trailing groups (which cannot produce merges since their sizes
    are 0). ``V`` is padded to a multiple of ``C`` with the id ``-1``.
    """
    num_nodes = shingle.shape[0]
    dead = (size <= 0).astype(jnp.int32)
    tie = jax.random.permutation(rng, num_nodes).astype(jnp.int32)
    ids = jnp.arange(num_nodes, dtype=jnp.int32)
    # lexicographic: (dead, shingle, random) — three int32 keys
    _, _, _, order = jax.lax.sort((dead, shingle, tie, ids), num_keys=3)
    pad = (-num_nodes) % group_size
    if pad:
        order = jnp.concatenate([order, jnp.full((pad,), -1, jnp.int32)])
    return order.reshape(-1, group_size)


def chunk_groups_lean(shingle: jax.Array, group_size: int) -> jax.Array:
    """2-key variant of :func:`chunk_groups` (§Perf ssumm iteration 1).

    Requires shingles that already carry the dead sentinel (``num_nodes``
    for dead ids — what ``supernode_shingles``/``_local_supernode_shingles``
    produce), so the (dead, …) key is redundant; id order breaks ties
    (randomness comes from the per-iteration re-draw of ``h``). Halves the
    bytes moved by the dominant [V]-sized sort."""
    num_nodes = shingle.shape[0]
    ids = jnp.arange(num_nodes, dtype=jnp.int32)
    _, order = jax.lax.sort((shingle, ids), num_keys=2)
    pad = (-num_nodes) % group_size
    if pad:
        order = jnp.concatenate([order, jnp.full((pad,), -1, jnp.int32)])
    return order.reshape(-1, group_size)


@jax.named_scope("shingles")
def build_groups(
    src: jax.Array,
    dst: jax.Array,
    state: SummaryState,
    rng: jax.Array,
    group_size: int,
) -> jax.Array:
    """Candidate groups from subnode-level shingles (single-device path)."""
    k_shingle, k_tie = jax.random.split(rng)
    sh = supernode_shingles(src, dst, state, k_shingle)
    return chunk_groups(sh, state.size, k_tie, group_size)


@jax.named_scope("shingles")
def build_groups_from_pairs(
    plo: jax.Array,
    phi: jax.Array,
    pvalid: jax.Array,
    size: jax.Array,
    rng: jax.Array,
    group_size: int,
) -> jax.Array:
    """Candidate groups from *supergraph-level* shingles.

    Distributed path: each owner device holds the full superedge adjacency
    of its owned supernodes, so ``f(A) = min(h(A), min_{{A,B}∈P} h(B))`` is
    computable locally and exactly. This lifts the paper's subnode shingle
    to the summary graph (the SWeG-style variant); 2-hop locality in the
    supergraph implies 2-hop locality in G.
    """
    num_nodes = size.shape[0]
    k_shingle, k_tie = jax.random.split(rng)
    h = jax.random.permutation(k_shingle, num_nodes).astype(jnp.int32)
    f = h
    ok = pvalid & (plo != phi)
    sent = jnp.int32(num_nodes)
    f = f.at[jnp.where(ok, plo, sent)].min(
        jnp.where(ok, h[jnp.minimum(phi, num_nodes - 1)], sent), mode="drop"
    )
    f = f.at[jnp.where(ok, phi, sent)].min(
        jnp.where(ok, h[jnp.minimum(plo, num_nodes - 1)], sent), mode="drop"
    )
    return chunk_groups(f, size, k_tie, group_size)
