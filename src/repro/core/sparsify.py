"""Further sparsification (Sect. 3.2.4): drop superedges until Size(Ḡ) ≤ k.

Vectorized version of the paper's three steps:
  1. closed-form RE_p increase per kept superedge (footnote 4):
         ΔRE₁ = (2|E_AB|/|Π_AB| - 1)·|E_AB|      ΔRE₂² = |E_AB|²/|Π_AB|
  2. the ξ-th smallest increase Δ_ξ via an order statistic
     (``jnp.sort`` — the paper uses median-of-medians selection; on TPU a
     bitonic sort of the |P| ≤ |E| deltas is the hardware-native choice),
  3. drop every superedge with ΔRE ≤ Δ_ξ.

The module has two order-statistic backends (DESIGN.md §7):

  * ``jnp.sort`` for the single-host path (``further_sparsify``), and
  * :func:`radix_select_kth` — a bucketed/histogram selection over the
    order-preserving uint32 image of the float32 deltas — whose per-pass
    256-bin histogram can be ``psum``-ed across an edge-sharded mesh, so
    the distributed path finds the *exact* Δ_ξ without replicating or
    gathering the deltas.  All scalar inputs of the ξ computation
    (Size(Ḡ), |S|, |P|, ω_max) are exact integers-in-float32 under any
    reduction order, and Δ itself is computed from bit-identical (cnt, Π)
    on every path, so the resulting drop mask is bit-identical between the
    single-host sort and the distributed selection.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import costs
from repro.core.types import PairTable, SummaryState

# Radix passes over the 32-bit ordered key, most-significant first.
_RADIX_SHIFTS = (24, 16, 8, 0)
_RADIX_BINS = 256


def sparsify_deltas(cnt: jax.Array, pi: jax.Array, error_p: int) -> jax.Array:
    """Footnote-4 ΔRE_p of dropping each superedge (closed form).

    ``error_p == 2`` returns ΔRE₂² = |E_AB|²/|Π_AB| — same ordering as ΔRE₂.
    """
    sigma = cnt / jnp.maximum(pi, 1.0)
    if error_p == 1:
        return (2.0 * sigma - 1.0) * cnt
    return cnt * sigma


def sparsify_xi(
    size_bits: jax.Array,
    k_bits: float,
    num_supernodes: jax.Array,
    omega_max: jax.Array,
) -> jax.Array:
    """ξ — how many superedges must go to bring Size(Ḡ) within ``k_bits``.

    Each dropped superedge saves one per-superedge record of
    ``2log₂|S| + log₂ω_max`` bits (constant except the ω_max edge — paper
    note), so ξ = ⌈(Size(Ḡ) − k) / unit⌉.
    """
    s_count = jnp.maximum(num_supernodes, 2.0)
    w_max = jnp.maximum(omega_max, 2.0)
    unit = 2.0 * jnp.log2(s_count) + jnp.log2(w_max)
    over = jnp.maximum(size_bits - k_bits, 0.0)
    return jnp.ceil(over / unit).astype(jnp.int32)


def drop_from_threshold(
    keep: jax.Array,
    delta: jax.Array,
    delta_xi: jax.Array,
    xi: jax.Array,
    p_count: jax.Array,
) -> jax.Array:
    """Step 3: drop kept superedges with ΔRE ≤ Δ_ξ (plus the degenerate
    branch: when even dropping all |P| superedges cannot reach k, drop all).
    """
    drop = keep & (delta <= delta_xi) & (xi > 0)
    return jnp.where(xi >= p_count, keep, drop)


# ---------------------------------------------------------------------------
# Order-preserving float32 ↔ uint32 maps + histogram-bucketed selection
# ---------------------------------------------------------------------------


def ordered_key_from_f32(x: jax.Array) -> jax.Array:
    """Monotone injection float32 → uint32 (IEEE-754 total order trick):
    flip the sign bit of non-negatives, all bits of negatives."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    neg = u >= jnp.uint32(0x80000000)
    return jnp.where(neg, ~u, u | jnp.uint32(0x80000000))


def f32_from_ordered_key(key: jax.Array) -> jax.Array:
    """Inverse of :func:`ordered_key_from_f32`."""
    key = key.astype(jnp.uint32)
    neg = key < jnp.uint32(0x80000000)
    u = jnp.where(neg, ~key, key ^ jnp.uint32(0x80000000))
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def radix_select_kth(keys: jax.Array, valid: jax.Array, k: jax.Array,
                     reduce_hist=None) -> jax.Array:
    """The ``k``-th smallest (0-based) valid uint32 key, by 4 radix passes.

    Each pass histograms the next 8 bits of the keys still matching the
    resolved prefix and descends into the bucket containing rank ``k``.
    ``reduce_hist`` merges the int32[256] histogram across shards (e.g.
    ``lambda h: jax.lax.psum(h, axis)``); identity when None — this is the
    only cross-shard communication of the distributed selection: 4 psums of
    256 ints replace a replicated sort of |E| floats.

    Caller guarantees ``0 ≤ k < #valid``; out-of-range ranks return an
    unspecified key (the degenerate ξ branches never read it).
    """
    if reduce_hist is None:
        reduce_hist = lambda h: h
    keys = keys.astype(jnp.uint32)
    prefix = jnp.uint32(0)
    rank = k.astype(jnp.int32)
    for shift in _RADIX_SHIFTS:
        high_mask = jnp.uint32((0xFFFFFFFF << (shift + 8)) & 0xFFFFFFFF)
        active = valid & ((keys & high_mask) == (prefix & high_mask))
        digit = ((keys >> shift) & jnp.uint32(0xFF)).astype(jnp.int32)
        hist = jnp.zeros((_RADIX_BINS,), jnp.int32).at[digit].add(
            jnp.where(active, 1, 0)
        )
        hist = reduce_hist(hist)
        cum = jnp.cumsum(hist)
        d = jnp.argmax(cum > rank).astype(jnp.int32)
        below = jnp.where(d > 0, cum[jnp.maximum(d - 1, 0)], 0)
        rank = rank - below
        prefix = prefix | (d.astype(jnp.uint32) << shift)
    return prefix


def select_delta_xi(delta: jax.Array, keep: jax.Array, xi: jax.Array,
                    reduce_hist=None) -> jax.Array:
    """Δ_ξ — the ξ-th smallest kept delta — via histogram selection.

    Returns the threshold as float32 so the ``delta ≤ Δ_ξ`` comparison runs
    in the float domain, exactly like the sort-based path.
    """
    keys = ordered_key_from_f32(delta)
    key_xi = radix_select_kth(keys, keep, jnp.maximum(xi - 1, 0), reduce_hist)
    return f32_from_ordered_key(key_xi)


# ---------------------------------------------------------------------------
# Single-host driver (sort-based order statistic)
# ---------------------------------------------------------------------------


@jax.named_scope("sparsify")
def further_sparsify(
    pt: PairTable,
    state: SummaryState,
    num_nodes: int,
    num_edges: int,
    k_bits: float,
    cbar_mode: str = "tight",
    re_guard: int = 1,
    error_p: int = 1,
):
    """Compute the drop mask that brings Size(Ḡ) within ``k_bits``.

    Returns ``(drop_mask bool[E], metrics_after dict)``.
    """
    metrics = costs.summary_metrics(
        pt, state, num_nodes, num_edges, cbar_mode=cbar_mode, re_guard=re_guard
    )
    keep = metrics["keep"]
    pi = costs.pair_pi(pt, state.size)
    delta = sparsify_deltas(pt.cnt, pi, error_p)
    xi = sparsify_xi(
        metrics["size_bits"], k_bits, metrics["num_supernodes"],
        metrics["omega_max"],
    )

    masked = jnp.where(keep, delta, jnp.inf)
    order = jnp.sort(masked)
    p_count = metrics["num_superedges"].astype(jnp.int32)
    xi_idx = jnp.clip(xi - 1, 0, masked.shape[0] - 1)
    delta_xi = order[xi_idx]
    drop = drop_from_threshold(keep, delta, delta_xi, xi, p_count)

    after = costs.summary_metrics(
        pt,
        state,
        num_nodes,
        num_edges,
        cbar_mode=cbar_mode,
        re_guard=re_guard,
        drop_mask=drop,
    )
    return drop, after
