"""Pallas TPU kernel: pairwise merge-gain matrices for candidate groups.

This is the compute hot spot of SSumM (DESIGN.md §5): per outer iteration it
evaluates ``O(Σ_g C²·U)`` fused entropy-cost terms. The kernel processes one
candidate group per grid step, keeping that group's union-space tables in
VMEM:

    VMEM working set  ≈ (C·U [m] + C·U [merged] + C·U [mask] + 4·C·C) · 4 B
    defaults C=32, U=128 → ≈ 0.07 MB  (≪ 16 MB VMEM/core)

Members run down the sublanes and union columns along the lanes; the
arithmetic is branch-free (`where` selects), so the body maps to a dense
VPU pipeline. The per-pair loop is a ``fori_loop`` over partners ``j``
with a full ``(C, U)`` vector body — C² scalar iterations are never emitted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _f_cost(cnt, pi, cbar, log2v):
    """min(C̄ + entropy bits, explicit bits) — branch-free (Eq. 11/12)."""
    pi_f = pi.astype(jnp.float32)
    safe_pi = jnp.maximum(pi_f, 1.0)
    sigma = jnp.clip(cnt / safe_pi, 0.0, 1.0)
    xlogx = jnp.where(sigma > 0.0, sigma * jnp.log2(jnp.maximum(sigma, 1e-38)), 0.0)
    ylogy = jnp.where(
        sigma < 1.0, (1.0 - sigma) * jnp.log2(jnp.maximum(1.0 - sigma, 1e-38)), 0.0
    )
    ent = jnp.where(
        (pi_f > 0.0) & (cnt > 0.0) & (cnt < pi_f), -pi_f * (xlogx + ylogy), 0.0
    )
    c1 = cbar + ent
    c2 = 2.0 * cnt * log2v
    return jnp.where(cnt > 0.0, jnp.minimum(c1, c2), 0.0)


def _merge_gain_kernel(
    scal_ref,  # f32[2] SMEM      (cbar, log2v)
    m_ref,  # f32[1, C, U]
    n_ref,  # f32[1, C, 1]
    s_ref,  # f32[1, C, 1]
    t_ref,  # f32[1, C, 1]
    nu_ref,  # f32[1, 1, U]
    cidx_ref,  # i32[1, C, 1]
    w_ref,  # f32[1, C, C]
    rel_ref,  # f32[1, C, C] out
    red_ref,  # f32[1, C, C] out
):
    """One group. Member ``i`` runs down the sublanes as ``(C, 1)`` columns;
    the loop walks partners ``j`` and fills column ``j`` of the outputs.
    Every value stays 2-D, and the per-``j`` operands are ref row loads or
    exact masked sums (Mosaic has no dynamic slice of a loaded value)."""
    cbar = scal_ref[0]
    log2v = scal_ref[1]
    m = m_ref[0]  # (C, U)
    n = n_ref[0]  # (C, 1)
    s = s_ref[0]
    t = t_ref[0]
    nu = nu_ref[0]  # (1, U)
    cidx = cidx_ref[0]  # (C, 1)
    w = w_ref[0]  # (C, C)
    c, u = m.shape

    f = functools.partial(_f_cost, cbar=cbar, log2v=log2v)

    # exact-tail bookkeeping (held in registers/VMEM for the whole group)
    row_cost = jnp.sum(f(m, n * nu), axis=-1, keepdims=True)  # (C, 1)
    self_cost = f(s, n * (n - 1.0) * 0.5)
    tail = jnp.maximum(t - row_cost - self_cost, 0.0)

    ucols = jax.lax.broadcasted_iota(jnp.int32, (c, u), 1)
    onehot = (ucols == cidx).astype(jnp.float32)  # (C, U)
    ulanes = jax.lax.broadcasted_iota(jnp.int32, (1, u), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    ccols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    def per_partner(j, carry):
        rel, red = carry
        mj = m_ref[0, pl.ds(j, 1), :]  # (1, U)
        nj = n_ref[0, pl.ds(j, 1), :]  # (1, 1)
        sj = s_ref[0, pl.ds(j, 1), :]
        tj = t_ref[0, pl.ds(j, 1), :]
        cj = cidx_ref[0, pl.ds(j, 1), :]
        ohj = (ulanes == cj).astype(jnp.float32)  # (1, U)
        is_j = rows == j  # (C, 1)
        tlj = jnp.sum(jnp.where(is_j, tail, 0.0), axis=0, keepdims=True)
        wj = jnp.sum(jnp.where(ccols == j, w, 0.0), axis=-1, keepdims=True)

        merged_cnt = m + mj  # (C, U)
        npair = n + nj  # (C, 1)
        fv = f(merged_cnt, npair * nu)
        mask = 1.0 - onehot - ohj
        cross = jnp.sum(fv * mask, axis=-1, keepdims=True)  # (C, 1)

        self_m = f(s + sj + wj, npair * (npair - 1.0) * 0.5)
        merged = cross + self_m + tail + tlj
        denom = t + tj - f(wj, n * nj)
        valid = (n > 0.0) & (nj > 0.0) & ~is_j & (denom > 1e-6)
        rel_j = jnp.where(valid, 1.0 - merged / jnp.maximum(denom, 1e-6),
                          -jnp.inf)
        red_j = jnp.where(valid, denom - merged, 0.0)
        at_j = ccols == j
        return jnp.where(at_j, rel_j, rel), jnp.where(at_j, red_j, red)

    init = (jnp.zeros((c, c), jnp.float32), jnp.zeros((c, c), jnp.float32))
    rel, red = jax.lax.fori_loop(0, c, per_partner, init)
    rel_ref[0] = rel
    red_ref[0] = red


def merge_gain_pallas(
    m: jax.Array,  # f32[G, C, U]
    n: jax.Array,  # f32[G, C]
    s: jax.Array,  # f32[G, C]
    t: jax.Array,  # f32[G, C]
    n_u: jax.Array,  # f32[G, U]
    cidx: jax.Array,  # i32[G, C]
    w: jax.Array,  # f32[G, C, C]
    cbar: jax.Array,
    log2v: jax.Array,
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Grid over groups; one group's tables per program, VMEM resident.

    Per-member vectors enter as ``(G, C, 1)`` columns and ``n_u`` as a
    ``(G, 1, U)`` row, so every block's last two dims equal the array's
    (the TPU tiling rule); the two scalars ride in SMEM."""
    g, c, u = m.shape
    scal = jnp.stack([cbar.astype(jnp.float32), log2v.astype(jnp.float32)])
    col = lambda x: x.reshape(g, c, 1)  # noqa: E731
    group = lambda i: (i, 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # (cbar, log2v), whole
        pl.BlockSpec((1, c, u), group),  # m
        pl.BlockSpec((1, c, 1), group),  # n
        pl.BlockSpec((1, c, 1), group),  # s
        pl.BlockSpec((1, c, 1), group),  # t
        pl.BlockSpec((1, 1, u), group),  # n_u
        pl.BlockSpec((1, c, 1), group),  # cidx
        pl.BlockSpec((1, c, c), group),  # w
    ]
    out_spec = pl.BlockSpec((1, c, c), group)
    out_shape = jax.ShapeDtypeStruct((g, c, c), jnp.float32)
    rel, red = pl.pallas_call(
        _merge_gain_kernel,
        grid=(g,),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
        name="ssumm_merge_gain",
    )(scal, m, col(n), col(s), col(t), n_u.reshape(g, 1, u), col(cidx), w)
    return rel, red
