"""MDL cost function of SSumM (Sect. 3.1, Eq. 5–16) in closed, vectorized form.

Key identity exploited throughout (DESIGN.md §4): given a partition ``S``,
the optimal superedge set ``P*(S)`` and every cost/size/error quantity are
closed-form per supernode pair ``{A,B}`` from only two aggregates:

    cnt = |E_AB|   (number of subedges between A and B)
    pi  = |Π_AB|   (number of possible subedges: n_A·n_B, or n_A(n_A-1)/2)

so the whole evaluation reduces to one sort of the immutable edge list,
whose runs are the pairs — no |V|² adjacency matrices anywhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import PairTable, SummaryState
from repro.utils import boundaries_from_keys, segment_length_at_start

# ---------------------------------------------------------------------------
# Entropy encodings (Eq. 9, Eq. 10)
# ---------------------------------------------------------------------------


def entropy_bits(cnt: jax.Array, pi: jax.Array) -> jax.Array:
    """Cost₍₁₎ without C̄: ``-|Π|(σlog₂σ + (1-σ)log₂(1-σ))``, Eq. (9).

    Guarded so that σ∈{0,1} (and Π=0) contribute exactly 0 bits.
    """
    pi = pi.astype(jnp.float32)
    cnt = cnt.astype(jnp.float32)
    safe_pi = jnp.maximum(pi, 1.0)
    sigma = jnp.clip(cnt / safe_pi, 0.0, 1.0)
    # x*log2(x) with the 0·log0 := 0 convention.
    xlogx = jnp.where(sigma > 0.0, sigma * jnp.log2(jnp.maximum(sigma, 1e-38)), 0.0)
    ylogy = jnp.where(
        sigma < 1.0, (1.0 - sigma) * jnp.log2(jnp.maximum(1.0 - sigma, 1e-38)), 0.0
    )
    h = -(xlogx + ylogy)
    return jnp.where((pi > 0.0) & (cnt > 0.0) & (cnt < pi), pi * h, 0.0)


def explicit_bits(cnt: jax.Array, log2v: jax.Array) -> jax.Array:
    """Cost₍₂₎: ``2|E_AB|log₂|V|``, Eq. (10)."""
    return 2.0 * cnt.astype(jnp.float32) * log2v


def pair_cost_star(
    cnt: jax.Array, pi: jax.Array, cbar: jax.Array, log2v: jax.Array
) -> jax.Array:
    """Optimal per-pair description cost: ``min(C̄ + Cost₍₁₎, Cost₍₂₎)`` (Eq. 11/12).

    ``cbar`` is 2log₂|V|+log₂|E| (paper) or the footnote-3 tighter bound.
    Pairs with cnt == 0 cost exactly 0 under either encoding.
    """
    c1 = cbar + entropy_bits(cnt, pi)
    c2 = explicit_bits(cnt, log2v)
    return jnp.where(cnt > 0.0, jnp.minimum(c1, c2), 0.0)


def keep_superedge(
    cnt: jax.Array,
    pi: jax.Array,
    cbar: jax.Array,
    log2v: jax.Array,
    re_guard: int,
) -> jax.Array:
    """Eq. (11) decision: keep {A,B} ∈ P iff entropy encoding is cheaper.

    ``re_guard`` implements footnote 3's "never creates superedges that
    increase RE_p": dropping changes RE₁ by cnt(2σ-1) and RE₂² by cnt·σ
    (footnote 4) — keeping is allowed only when dropping would not shrink
    the error.
    """
    mdl_keep = (cbar + entropy_bits(cnt, pi)) < explicit_bits(cnt, log2v)
    keep = mdl_keep & (cnt > 0.0)
    if re_guard == 1:
        sigma = cnt / jnp.maximum(pi, 1.0)
        keep = keep & (2.0 * sigma - 1.0 >= 0.0)
    # re_guard == 2 never binds: dropping always increases RE₂ (σ>0).
    return keep


# ---------------------------------------------------------------------------
# Pair table: partition → {(A,B) : |E_AB| > 0} via one sort, in sorted order
# ---------------------------------------------------------------------------


@jax.named_scope("pair_table")
def build_pair_table(src: jax.Array, dst: jax.Array, state: SummaryState) -> PairTable:
    """Aggregate the edge list into per-supernode-pair subedge counts.

    Sorting uses two int32 keys (``lo``, ``hi``) via ``lax.sort`` so no int64
    composite key is needed (TPU-friendly). The table stays in that sorted
    order: each pair is one run, its first entry is the pair's valid row and
    holds the run's length; the run's other entries are invalid with
    ``cnt`` 0. No scatter compacts the runs (the TPU applies a scatter one
    index at a time).
    """
    su = state.node2super[src]
    sv = state.node2super[dst]
    lo = jnp.minimum(su, sv)
    hi = jnp.maximum(su, sv)
    lo_s, hi_s = jax.lax.sort((lo, hi), num_keys=2)
    is_new = boundaries_from_keys(lo_s, hi_s)
    # exact in float32: E < 2^24
    cnt = segment_length_at_start(is_new).astype(jnp.float32)
    return PairTable(lo=lo_s, hi=hi_s, cnt=cnt, valid=is_new)


def pair_pi(pt: PairTable, size: jax.Array) -> jax.Array:
    """|Π_AB| per pair: n_A·n_B for A≠B, n_A(n_A-1)/2 for the self pair."""
    na = size[pt.lo].astype(jnp.float32)
    nb = size[pt.hi].astype(jnp.float32)
    is_self = pt.lo == pt.hi
    pi = jnp.where(is_self, na * (na - 1.0) * 0.5, na * nb)
    return jnp.where(pt.valid, pi, 0.0)


# ---------------------------------------------------------------------------
# Global quantities: Eq. (3), Eq. (4), Eq. (14), RE_p (Eq. 2 closed form)
# ---------------------------------------------------------------------------


def input_size_bits(num_nodes: int, num_edges: int) -> float:
    """Size(G) = 2|E|log₂|V|, Eq. (3)."""
    return 2.0 * num_edges * float(jnp.log2(jnp.float32(num_nodes)))


def cbar_value(
    mode: str,
    num_nodes: int,
    num_edges: int,
    num_supernodes: jax.Array,
    omega_max: jax.Array,
) -> jax.Array:
    """C̄ — per-superedge model cost. Paper: Eq. (6); tight: footnote 3."""
    if mode == "paper":
        v = jnp.float32(num_nodes)
        e = jnp.float32(num_edges)
        return 2.0 * jnp.log2(v) + jnp.log2(jnp.maximum(e, 2.0))
    s = jnp.maximum(num_supernodes.astype(jnp.float32), 2.0)
    w = jnp.maximum(omega_max.astype(jnp.float32), 2.0)
    return 2.0 * jnp.log2(s) + jnp.log2(w)


@jax.named_scope("summary_metrics")
def summary_metrics(
    pt: PairTable,
    state: SummaryState,
    num_nodes: int,
    num_edges: int,
    cbar_mode: str = "tight",
    re_guard: int = 1,
    drop_mask: jax.Array | None = None,
) -> dict[str, jax.Array]:
    """All evaluation quantities for the current partition, in one pass.

    **Paper P semantics** (Alg. 1 lines 2 & 7): P is initialized to *all*
    edges, and superedges are re-decided (Eq. 11 + RE guard) only when they
    are adjacent to a newly merged supernode. Since supernode sizes are
    monotone, "was ever re-decided" ≡ ``size[A] > 1 or size[B] > 1`` — so the
    paper's stateful P is recoverable statelessly from the current partition:
    untouched singleton–singleton pairs stay in P unconditionally.

    ``drop_mask`` (bool[E] aligned with ``pt``) marks superedges removed by
    the *further sparsification* phase on top of this.

    Returns exact values of:
      * ``size_bits``  — Eq. (4) with the realized |S|, |P|, ω_max
      * ``mdl_cost``   — Eq. (5) model + data bits over the realized P
      * ``re1``/``re2``— Eq. (2), normalized by |V|(|V|-1) (footnote 5)
      * bookkeeping (num_supernodes, num_superedges, omega_max)
    """
    v = jnp.float32(num_nodes)
    log2v = jnp.log2(v)
    s_count = jnp.sum(state.size > 0).astype(jnp.float32)
    pi = pair_pi(pt, state.size)
    omega_max_all = jnp.max(jnp.where(pt.valid, pt.cnt, 0.0))
    cbar = cbar_value(cbar_mode, num_nodes, num_edges, s_count, omega_max_all)
    touched = (state.size[pt.lo] > 1) | (state.size[pt.hi] > 1)
    decided = keep_superedge(pt.cnt, pi, cbar, log2v, re_guard)
    keep = jnp.where(touched, decided, pt.cnt > 0.0) & pt.valid
    if drop_mask is not None:
        keep = keep & ~drop_mask

    cntk = jnp.where(keep, pt.cnt, 0.0)
    sigma = jnp.where(keep, pt.cnt / jnp.maximum(pi, 1.0), 0.0)

    # --- Eq. (4): realized summary size --------------------------------
    p_count = jnp.sum(keep.astype(jnp.float32))
    omega_max = jnp.max(cntk)
    log2s = jnp.log2(jnp.maximum(s_count, 2.0))
    log2w = jnp.log2(jnp.maximum(omega_max, 2.0))
    size_bits = p_count * (2.0 * log2s + log2w) + v * log2s

    # --- Eq. (14): MDL description cost (upper-bound C̄ per the paper) ---
    log2e = jnp.log2(jnp.maximum(jnp.float32(num_edges), 2.0))
    cbar_paper = 2.0 * log2v + log2e
    kept_bits = cbar_paper + entropy_bits(pt.cnt, pi)
    drop_bits = explicit_bits(pt.cnt, log2v)
    per_pair = jnp.where(keep, kept_bits, jnp.where(pt.valid, drop_bits, 0.0))
    mdl_cost = v * log2v + jnp.sum(per_pair)

    # --- Eq. (2) closed forms (unordered; ×2 for the full matrix) -------
    re1_kept = 2.0 * cntk * (1.0 - sigma)
    re2_kept = cntk * (1.0 - sigma)
    dropped_cnt = jnp.where(pt.valid & ~keep, pt.cnt, 0.0)
    re1_sum = jnp.sum(re1_kept) + jnp.sum(dropped_cnt)
    re2_sq = jnp.sum(re2_kept) + jnp.sum(dropped_cnt)
    denom = v * (v - 1.0)
    re1 = 2.0 * re1_sum / denom
    re2 = jnp.sqrt(2.0 * re2_sq) / denom

    return {
        "size_bits": size_bits,
        "mdl_cost": mdl_cost,
        "re1": re1,
        "re2": re2,
        "num_supernodes": s_count,
        "num_superedges": p_count,
        "omega_max": omega_max,
        "keep": keep,
        "cbar": cbar,
        "membership_bits": v * log2s,
    }


def supernode_total_costs(
    pt: PairTable,
    pi: jax.Array,
    cbar: jax.Array,
    log2v: jax.Array,
    num_nodes: int,
) -> jax.Array:
    """``Cost*_A(S)`` per supernode id (Eq. 16): scatter each pair's optimal
    cost to both endpoints (self pairs once)."""
    cost = jnp.where(pt.valid, pair_cost_star(pt.cnt, pi, cbar, log2v), 0.0)
    out = jnp.zeros((num_nodes,), jnp.float32)
    out = out.at[pt.lo].add(cost)
    is_nonself = pt.lo != pt.hi
    out = out.at[pt.hi].add(jnp.where(is_nonself, cost, 0.0))
    return out
