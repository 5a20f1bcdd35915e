"""Segment helpers for sorted-key dataflow (the TPU-native workhorse of repro.core).

Everything here operates on *sorted* key arrays with fixed shapes and is
jit-compatible. These primitives replace the hash-map bookkeeping of the
reference CPU implementation of SSumM with sort/scan dataflow.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def cummax(x: jax.Array, axis: int = 0) -> jax.Array:
    """Inclusive cumulative maximum along ``axis``."""
    return jax.lax.cummax(x, axis=axis)


def segment_start(is_new: jax.Array) -> jax.Array:
    """Index of the start of each element's segment.

    ``is_new[i]`` is True when element ``i`` opens a new segment (element 0
    must be True). Returns ``start[i]`` = index of the first element of the
    segment containing ``i``.
    """
    n = is_new.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    return cummax(jnp.where(is_new, idx, 0))


def rank_in_segment(is_new: jax.Array) -> jax.Array:
    """0-based rank of each element within its segment (sorted layout)."""
    n = is_new.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    return idx - segment_start(is_new)


def segment_length_at_start(is_new: jax.Array) -> jax.Array:
    """Length of each segment at its first element, 0 elsewhere (sorted layout).

    A segment ends where the next element opens one (or at the array's end);
    a reverse cumulative minimum carries that end back to every element, so
    no scatter is needed. Returns int32.
    """
    n = is_new.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    closes = jnp.concatenate([is_new[1:], jnp.ones((1,), bool)])
    end = jax.lax.cummin(jnp.where(closes, idx, n), reverse=True)
    return jnp.where(is_new, end - idx + 1, 0)


def boundaries_from_keys(*keys: jax.Array) -> jax.Array:
    """``is_new`` flags for a lexicographically sorted multi-key array."""
    ks = keys[0]
    n = ks.shape[0]
    new = jnp.zeros((n,), dtype=bool).at[0].set(True)
    for k in keys:
        prev = jnp.concatenate([k[:1], k[:-1]])
        new = new | (k != prev)
    return new


def segment_ids_from_boundaries(is_new: jax.Array) -> jax.Array:
    """Contiguous segment ids (0-based) from ``is_new`` flags."""
    return jnp.cumsum(is_new.astype(jnp.int32)) - 1
