"""Merging & sparsification phase (Sect. 3.2.3, Alg. 2) — TPU-native form.

One outer iteration = one *parallel coarsening round*: every candidate group
scores all of its pairs with the Pallas merge-gain kernel and merges a
maximal set of mutually-best pairs whose Relative_Reduction (Eq. 20) exceeds
the annealing threshold θ(t) (Eq. 21). Superedge sparsification is implicit:
the optimal encoding P*(S) is recomputed in closed form whenever costs or
sizes are evaluated (Eq. 11), which is exactly the paper's "add superedges
selectively so that the cost is minimized" step.

Deviation from the sequential paper loop (DESIGN.md §3 ⚠): instead of
merging repeatedly inside one group while others wait, all groups across the
whole graph merge one matching simultaneously; the T outer iterations with
re-randomized shingles provide the repeated chances the sequential loop gets
within an iteration. Matching via mutual-argmax guarantees the merge set is
disjoint, so applying it is a single gather.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import costs, shingles, tables
from repro.core.types import SummaryConfig, SummaryState
from repro.kernels import ops as kops


def theta_schedule(t: jax.Array, big_t: int) -> jax.Array:
    """Eq. (21): θ(t) = (1+t)⁻¹ for t < T, 0 at t ≥ T."""
    return jnp.where(t < big_t, 1.0 / (1.0 + t.astype(jnp.float32)), 0.0)


@jax.named_scope("matching")
def select_matching(
    rel: jax.Array,  # f32[G, C, C]
    members: jax.Array,  # i32[G, C]
    theta: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Mutually-best pairs above θ → disjoint merge list (a_ids, b_ids, sel)."""
    g, c, _ = rel.shape
    best_j = jnp.argmax(rel, axis=-1).astype(jnp.int32)  # [G, C]
    best_v = jnp.max(rel, axis=-1)  # [G, C]
    idx = jnp.arange(c, dtype=jnp.int32)[None, :]
    partner_best = jnp.take_along_axis(best_j, best_j, axis=1)
    mutual = partner_best == idx
    accept = mutual & (best_v > theta) & (idx < best_j)
    a = jnp.take_along_axis(members, idx, axis=1)
    b = jnp.take_along_axis(members, best_j, axis=1)
    accept = accept & (a >= 0) & (b >= 0)
    return a.reshape(-1), b.reshape(-1), accept.reshape(-1)


@jax.named_scope("matching")
def apply_merges(
    state: SummaryState, a: jax.Array, b: jax.Array, sel: jax.Array
) -> tuple[SummaryState, jax.Array]:
    """Union each selected pair: supernode ``b`` is absorbed into ``a``."""
    v = state.node2super.shape[0]
    b_idx = jnp.where(sel, b, v)  # OOB → dropped
    a_idx = jnp.where(sel, a, v)
    parent = jnp.arange(v, dtype=jnp.int32).at[b_idx].set(
        jnp.where(sel, a, 0), mode="drop"
    )
    node2super = parent[state.node2super]
    moved = jnp.where(sel, state.size[jnp.minimum(b, v - 1)], 0)
    size = state.size.at[a_idx].add(moved, mode="drop")
    size = size.at[b_idx].set(0, mode="drop")
    nmerges = jnp.sum(sel.astype(jnp.int32))
    return (
        SummaryState(node2super=node2super, size=size, rng=state.rng, t=state.t),
        nmerges,
    )


def scoring_operands(
    src: jax.Array,
    dst: jax.Array,
    state: SummaryState,
    cfg: SummaryConfig,
    k_groups: jax.Array,
) -> tuple[tables.GroupTables, dict[str, jax.Array]]:
    """Pair table, Eq. (2)/(4) metrics and the candidate groups' merge-gain
    operands of one round (Alg. 1 line 5) — everything the kernel reads."""
    v = state.node2super.shape[0]
    e = src.shape[0]
    pt = costs.build_pair_table(src, dst, state)
    metrics = costs.summary_metrics(
        pt, state, v, e, cbar_mode=cfg.cbar_mode, re_guard=cfg.re_guard
    )
    groups = shingles.build_groups(src, dst, state, k_groups, cfg.group_size)
    gt = tables.build_group_tables(
        pt, state, groups, cfg.max_neighbors, cfg.union_size, metrics["cbar"], v
    )
    return gt, metrics


def merge_iteration(
    src: jax.Array,
    dst: jax.Array,
    state: SummaryState,
    cfg: SummaryConfig,
    theta: jax.Array,
) -> tuple[SummaryState, dict[str, jax.Array]]:
    """One full candidate-generation + merging round (Alg. 1 lines 5–7)."""
    v = state.node2super.shape[0]
    rng, k_groups = jax.random.split(state.rng)
    state = SummaryState(
        node2super=state.node2super, size=state.size, rng=rng, t=state.t
    )

    gt, metrics = scoring_operands(src, dst, state, cfg, k_groups)
    rel, red = kops.merge_gain(
        gt.m,
        gt.n,
        gt.s,
        gt.t,
        gt.n_u,
        gt.cidx,
        gt.w,
        metrics["cbar"],
        jnp.log2(jnp.float32(v)),
        backend=kops.resolve_kernel_backend(cfg.kernel_backend),
    )
    a, b, sel = select_matching(rel, gt.members, theta)
    new_state, nmerges = apply_merges(state, a, b, sel)
    # summed Eq. 20 absolute reduction (bits) of the accepted pairs: gather
    # each row's best-partner red — the same argmax select_matching used
    with jax.named_scope("matching"):
        best_j = jnp.argmax(rel, axis=-1)
        red_best = jnp.take_along_axis(red, best_j[..., None], axis=-1)[..., 0]
        total_reduction = jnp.sum(jnp.where(sel, red_best.reshape(-1), 0.0))
    new_state = SummaryState(
        node2super=new_state.node2super,
        size=new_state.size,
        rng=new_state.rng,
        t=state.t + 1,
    )
    stats = {
        "nmerges": nmerges,
        "size_bits": metrics["size_bits"],
        "mdl_cost": metrics["mdl_cost"],
        "re1": metrics["re1"],
        "re2": metrics["re2"],
        "num_supernodes": metrics["num_supernodes"],
        "num_superedges": metrics["num_superedges"],
        "total_reduction": total_reduction,
    }
    return new_state, stats
