"""Per-group neighbor tables: the operands of the merge-gain kernel.

For every candidate group of ``C`` supernodes we build a *dense union-space*
representation (DESIGN.md §5): the distinct neighbor supernodes of all group
members are assigned up to ``U`` columns, so the neighbor multiset of member
``i`` is a row ``M[i, :]`` and the neighbor multiset of a merged pair (i,j)
is simply ``M[i] + M[j]`` — turning the paper's sorted-list unions into MXU
friendly dense arithmetic.

The union space is built without a scatter or a gather over the group's
``C·D`` entries: one sort of the members' ids, a second sort that moves
the first occurrence of each id to the front (the ``U`` smallest distinct
ids, ascending), and compare-reduces
``M[i, j] = Σ_d cnt[i, d]·[id[i, d] = uid[j]]`` (and the within-group
counts against the member ids) that XLA fuses, so no ``[C, D, U]`` compare
is stored. Each sum adds one count to zeros, as an id occurs once in a
member's row (a repeated id adds its counts up), so the tables are exact.

Exactness contract: scoring sees the top-``D`` heaviest neighbors of each
member (≤ ``U`` union columns); everything that falls off the tables is
carried by the *exact* per-supernode totals ``t_A = Cost*_A(S)`` as a
``tail`` term that is held constant under a hypothetical merge (a lower
bound on the merged cost by Lemma B.1 — see DESIGN.md §3 ⚠). With
``D ≥ max degree`` the scoring is exact; tests enforce this against the
sequential oracle.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import costs
from repro.core.types import PairTable, SummaryState, _pytree
from repro.utils import boundaries_from_keys, rank_in_segment


@_pytree
@dataclasses.dataclass
class GroupTables:
    """Operands for one merge-gain evaluation over all groups."""

    m: jax.Array  # float32[G, C, U]  member→union-neighbor subedge counts
    n: jax.Array  # float32[G, C]    member supernode sizes (0 = padding)
    s: jax.Array  # float32[G, C]    member self-loop subedge counts
    t: jax.Array  # float32[G, C]    exact Cost*_A(S) totals
    n_u: jax.Array  # float32[G, U]  union-neighbor supernode sizes
    cidx: jax.Array  # int32[G, C]   member's own column in U (U = absent)
    w: jax.Array  # float32[G, C, C] within-group pair subedge counts
    members: jax.Array  # int32[G, C] supernode ids (-1 = padding)


def build_neighbor_tables(
    pt: PairTable, num_nodes: int, max_neighbors: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-``D`` heaviest neighbors per supernode + self-loop counts.

    Returns ``(nbr_id int32[V, D], nbr_cnt float32[V, D], self_cnt float32[V])``
    with ``nbr_id == V`` marking empty slots.
    """
    v, d = num_nodes, max_neighbors
    nonself = pt.valid & (pt.lo != pt.hi)
    # two directed entries per undirected pair
    owner = jnp.concatenate([pt.lo, pt.hi])
    other = jnp.concatenate([pt.hi, pt.lo])
    cnt = jnp.concatenate([pt.cnt, pt.cnt])
    val = jnp.concatenate([nonself, nonself])
    owner_k = jnp.where(val, owner, v)  # invalid entries last
    neg_cnt = jnp.where(val, -cnt, 0.0)
    owner_s, _, other_s, cnt_s, val_s = jax.lax.sort(
        (owner_k, neg_cnt, other, cnt, val.astype(jnp.int32)), num_keys=2
    )
    is_new = boundaries_from_keys(owner_s)
    rank = rank_in_segment(is_new)
    keep = (rank < d) & (val_s > 0)
    flat = jnp.where(keep, owner_s * d + rank, v * d)  # OOB → dropped
    nbr_id = jnp.full((v * d,), v, jnp.int32).at[flat].set(other_s, mode="drop")
    nbr_cnt = jnp.zeros((v * d,), jnp.float32).at[flat].set(cnt_s, mode="drop")

    is_self = pt.valid & (pt.lo == pt.hi)
    self_cnt = jnp.zeros((v,), jnp.float32).at[
        jnp.where(is_self, pt.lo, v)
    ].add(jnp.where(is_self, pt.cnt, 0.0), mode="drop")
    return nbr_id.reshape(v, d), nbr_cnt.reshape(v, d), self_cnt


def build_neighbor_tables_compact(
    plo: jax.Array,
    phi: jax.Array,
    cnt: jax.Array,
    valid: jax.Array,
    slot_of: jax.Array,  # int32[V]: global id → compact row (-1 = not owned)
    n_rows: int,
    num_nodes: int,
    max_neighbors: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-``D`` neighbor tables for a *subset* of supernodes (the owned
    rows of one device) — [n_rows, D] instead of [V, D], which is what lets
    the distributed path scale to web-size V (DESIGN.md §7).

    Same dataflow as :func:`build_neighbor_tables` with row indices mapped
    through ``slot_of``. Self-loop counts are returned per row.
    """
    v, d = num_nodes, max_neighbors
    nonself = valid & (plo != phi)
    owner = jnp.concatenate([plo, phi])
    other = jnp.concatenate([phi, plo])
    cnt2 = jnp.concatenate([cnt, cnt])
    row = slot_of[jnp.clip(owner, 0, v - 1)]
    val = jnp.concatenate([nonself, nonself]) & (row >= 0)
    row_k = jnp.where(val, row, n_rows)
    neg_cnt = jnp.where(val, -cnt2, 0.0)
    row_s, _, other_s, cnt_s, val_s = jax.lax.sort(
        (row_k, neg_cnt, other, cnt2, val.astype(jnp.int32)), num_keys=2
    )
    is_new = boundaries_from_keys(row_s)
    rank = rank_in_segment(is_new)
    keep = (rank < d) & (val_s > 0)
    flat = jnp.where(keep, row_s * d + rank, n_rows * d)
    nbr_id = jnp.full((n_rows * d + 1,), v, jnp.int32).at[flat].set(
        other_s, mode="drop")[:-1]
    nbr_cnt = jnp.zeros((n_rows * d + 1,), jnp.float32).at[flat].set(
        cnt_s, mode="drop")[:-1]

    is_self = valid & (plo == phi)
    self_row = slot_of[jnp.clip(plo, 0, v - 1)]
    ok_self = is_self & (self_row >= 0)
    self_cnt = jnp.zeros((n_rows + 1,), jnp.float32).at[
        jnp.where(ok_self, self_row, n_rows)
    ].add(jnp.where(ok_self, cnt, 0.0), mode="drop")[:-1]
    return nbr_id.reshape(n_rows, d), nbr_cnt.reshape(n_rows, d), self_cnt


def supernode_total_costs_compact(
    plo, phi, cnt, valid, slot_of, n_rows: int, num_nodes: int,
    sizes: jax.Array, cbar: jax.Array, log2v: jax.Array,
) -> jax.Array:
    """``Cost*_A(S)`` per owned row from the local pair records."""
    na = sizes[jnp.clip(plo, 0, num_nodes - 1)].astype(jnp.float32)
    nb = sizes[jnp.clip(phi, 0, num_nodes - 1)].astype(jnp.float32)
    pi = jnp.where(plo == phi, na * (na - 1.0) * 0.5, na * nb)
    cost = jnp.where(valid, costs.pair_cost_star(cnt, pi, cbar, log2v), 0.0)
    out = jnp.zeros((n_rows + 1,), jnp.float32)
    row_lo = jnp.where(valid, slot_of[jnp.clip(plo, 0, num_nodes - 1)], -1)
    row_hi = jnp.where(valid & (plo != phi),
                       slot_of[jnp.clip(phi, 0, num_nodes - 1)], -1)
    out = out.at[jnp.where(row_lo >= 0, row_lo, n_rows)].add(
        jnp.where(row_lo >= 0, cost, 0.0), mode="drop")
    out = out.at[jnp.where(row_hi >= 0, row_hi, n_rows)].add(
        jnp.where(row_hi >= 0, cost, 0.0), mode="drop")
    return out[:-1]


@jax.named_scope("group_tables")
def build_group_tables(
    pt: PairTable,
    state: SummaryState,
    groups: jax.Array,  # int32[G, C]
    max_neighbors: int,
    union_size: int,
    cbar: jax.Array,
    num_nodes: int,
) -> GroupTables:
    """Assemble the dense union-space operands for every group."""
    v = num_nodes
    d = max_neighbors

    nbr_id, nbr_cnt, self_cnt = build_neighbor_tables(pt, v, d)
    pi = costs.pair_pi(pt, state.size)
    log2v = jnp.log2(jnp.float32(v))
    t_all = costs.supernode_total_costs(pt, pi, cbar, log2v, v)
    return assemble_group_tables(
        nbr_id, nbr_cnt, self_cnt, t_all, state.size, groups,
        row_of_member=None, union_size=union_size, num_nodes=v,
    )


def assemble_group_tables(
    nbr_id: jax.Array,  # [N, D] neighbor *global* ids (V = empty)
    nbr_cnt: jax.Array,  # [N, D]
    self_cnt: jax.Array,  # [N]
    t_all: jax.Array,  # [N]
    sizes: jax.Array,  # [V] global supernode sizes
    groups: jax.Array,  # int32[G, C] *global* member ids (-1 = padding)
    row_of_member,  # int32[V] global id → table row, or None (row = id)
    union_size: int,
    num_nodes: int,
) -> GroupTables:
    """Union-space assembly shared by the local ([V,D] tables) and
    distributed-compact ([N_own,D] tables) paths."""
    v = num_nodes
    g_cnt, c = groups.shape
    u = union_size
    d = nbr_id.shape[-1]

    members = groups
    mvalid = members >= 0
    midx = jnp.where(mvalid, members, 0)
    rows = midx if row_of_member is None else jnp.clip(
        row_of_member[midx], 0, nbr_id.shape[0] - 1)
    n = jnp.where(mvalid, sizes[midx], 0).astype(jnp.float32)
    alive = n > 0
    if row_of_member is not None:
        alive = alive & (row_of_member[midx] >= 0)
        n = jnp.where(alive, n, 0.0)
    s = jnp.where(alive, self_cnt[rows], 0.0)
    t = jnp.where(alive, t_all[rows], 0.0)

    tab_id = jnp.where(alive[..., None], nbr_id[rows], v)  # [G, C, D]
    tab_cnt = jnp.where(alive[..., None], nbr_cnt[rows], 0.0)

    # ---- union space: the U smallest distinct ids, ascending -------------
    ids_s = jnp.sort(tab_id.reshape(g_cnt, c * d), axis=1)
    first = jnp.concatenate(
        [jnp.ones((g_cnt, 1), bool), ids_s[:, 1:] != ids_s[:, :-1]], axis=1
    )
    # a second sort moves the repeats, now V, behind the distinct ids (V
    # pads the columns where a group has fewer than U table slots)
    uid = jnp.sort(jnp.where(first, ids_s, v), axis=1)[:, :u]
    uid = jnp.pad(uid, ((0, 0), (0, u - uid.shape[1])), constant_values=v)
    n_u = jnp.where(uid < v, sizes[jnp.minimum(uid, v - 1)], 0).astype(
        jnp.float32
    )

    # member→union counts as a compare-reduce over each member's own row:
    # a column matches at most one slot of a row (a repeated id adds up)
    hit = (tab_id[..., None] == uid[:, None, None, :]) & (
        uid < v)[:, None, None, :]  # [G, C, D, U], fused into the sum
    m = jnp.sum(jnp.where(hit, tab_cnt[..., None], 0.0), axis=2)

    # member's own column in union space (U = absent)
    eq = (uid[:, None, :] == midx[:, :, None]) & alive[:, :, None]  # [G,C,U]
    found = jnp.any(eq, axis=-1)
    cidx = jnp.where(found, jnp.argmax(eq, axis=-1).astype(jnp.int32), u)

    # within-group pair counts from either row's table (max recovers entries
    # truncated out of one of the two rows); a member outside the union
    # (id -1 here) scores no within-group edge, as it has no column in m
    own = jnp.where(found, midx, -1)
    w1 = jnp.sum(jnp.where(
        tab_id[:, :, None, :] == own[:, None, :, None],
        tab_cnt[:, :, None, :], 0.0), axis=3)  # [G, C, C]
    w = jnp.maximum(w1, jnp.swapaxes(w1, 1, 2))

    return GroupTables(m=m, n=n, s=s, t=t, n_u=n_u, cidx=cidx, w=w, members=members)
