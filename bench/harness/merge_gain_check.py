"""The plain reference of the merge gain: Eq. (17) Reduction and Eq. (20)
Relative_Reduction of every candidate pair of a group, from the group's
operands, written from the paper's definitions and evaluated in float64
numpy (or, for the control, in a lower precision under ``jax.numpy``).

The program's kernel (``kernels/ops.py::merge_gain``) decides which
supernodes each round merges. The benchmark calls it after the window on
round 1's operands at the timed size, and compares a sample of groups drawn
from the run's seed with this reference:

- ``merge_gain_red_gap``: the largest gap of Reduction, as a share of the
  pair's scale ``t_i + t_j`` (its two members' exact costs, of which every
  term is a part);
- ``merge_gain_rel_gap``: the largest gap of Relative_Reduction where it is
  well posed, the denominator at least a hundredth of that scale;
- ``merge_gain_mask_wrong``: pairs scored that cannot be merged (padding,
  a member with itself), plus pairs left unscored whose denominator in the
  reference is a bit or more.
"""

from __future__ import annotations

import numpy as np

#: Groups of the sample, and groups evaluated at once (the reference's
#: [block, C, C, U] temporaries).
SAMPLE_GROUPS = 256
BLOCK_GROUPS = 16


def pair_cost(cnt, pi, cbar, log2v, xp, f):
    """Eq. (11)/(12): the cheaper of encoding a supernode pair's ``cnt``
    subedges among its ``pi`` possible ones by a superedge (C̄ plus the
    entropy of the density, nothing where it is 0 or 1) or by corrections
    alone (2·log₂|V| per subedge); nothing for a pair without subedges."""
    inner = (cnt > f(0)) & (cnt < pi)
    sigma = xp.where(inner, cnt / xp.where(inner, pi, f(1)), f(0.5))
    bits = -(sigma * xp.log2(sigma) + (f(1) - sigma) * xp.log2(f(1) - sigma))
    keep = cbar + xp.where(inner, pi * bits, f(0))
    return xp.where(cnt > f(0), xp.minimum(keep, f(2) * cnt * log2v), f(0))


def reference(m, n, s, t, n_u, cidx, w, cbar, log2v, xp=np,
              dtype=np.float64):
    """``(rel, red, denom)`` [G, C, C] of every member pair of each group,
    with every value and sum in ``dtype``. ``rel`` is -inf and ``red`` 0
    where the pair cannot be merged."""
    def f(x):
        return xp.asarray(x, dtype)

    m, n, s, t, n_u, w = map(f, (m, n, s, t, n_u, w))
    cbar, log2v = f(cbar), f(log2v)
    _, c, u = m.shape

    def cost(cnt, pi):
        return pair_cost(cnt, pi, cbar, log2v, xp, f)

    # each member's cost outside the group's union columns and its own pair
    row = xp.sum(cost(m, n[:, :, None] * n_u[:, None, :]), axis=-1)
    own = cost(s, n * (n - f(1)) / f(2))
    rest = xp.maximum(t - row - own, f(0))
    # the merged supernode's pairs with the union columns, leaving out the
    # columns of the two members themselves
    both = n[:, :, None] + n[:, None, :]
    cols = np.arange(u)[None, None, None, :]
    cidx = xp.asarray(cidx)
    outside = ((cols != cidx[:, :, None, None])
               & (cols != cidx[:, None, :, None]))
    merged_pairs = cost(m[:, :, None, :] + m[:, None, :, :],
                        both[..., None] * n_u[:, None, None, :])
    cross = xp.sum(xp.where(outside, merged_pairs, f(0)), axis=-1)
    inside = cost(s[:, :, None] + s[:, None, :] + w, both * (both - f(1)) / f(2))
    merged = cross + inside + rest[:, :, None] + rest[:, None, :]
    # Eq. (17): the two members' costs, their shared pair counted once
    denom = (t[:, :, None] + t[:, None, :]
             - cost(w, n[:, :, None] * n[:, None, :]))
    ok = ((n[:, :, None] > f(0)) & (n[:, None, :] > f(0))
          & xp.asarray(~np.eye(c, dtype=bool))[None] & (denom > f(1e-6)))
    rel = xp.where(ok, f(1) - merged / xp.where(ok, denom, f(1)), f(-np.inf))
    red = xp.where(ok, denom - merged, f(0))
    return rel, red, denom


def sample_groups(n: np.ndarray, seed: int, count: int = SAMPLE_GROUPS):
    """Indices, sorted, of up to ``count`` groups with two members or more,
    drawn from ``seed``."""
    real = np.flatnonzero((np.asarray(n) > 0).sum(axis=1) >= 2)
    rng = np.random.default_rng(seed)
    pick = rng.choice(real.size, size=min(count, real.size), replace=False)
    return np.sort(real[pick])


def _worse(a: float, b: float) -> float:
    """The larger, where a NaN is larger than anything."""
    return b if (b > a or b != b) else a


def readings(got_rel, got_red, operands, groups) -> dict:
    """The three numbers of the module docstring: ``(got_rel, got_red)``
    [G, C, C] (the program's answer, or the control's) of the sampled
    ``groups`` against the float64 reference."""
    m, n, s, t, n_u, cidx, w, cbar, log2v = operands
    red_gap = rel_gap = 0.0
    wrong = 0
    c = np.asarray(n).shape[1]
    for lo in range(0, groups.size, BLOCK_GROUPS):
        g = groups[lo:lo + BLOCK_GROUPS]
        rel, red, denom = (np.asarray(x, np.float64) for x in reference(
            *(np.asarray(x)[g] for x in (m, n, s, t, n_u, cidx, w)),
            cbar, log2v))
        p_rel = np.asarray(got_rel, np.float64)[g]
        p_red = np.asarray(got_red, np.float64)[g]
        tg = np.asarray(t, np.float64)[g]
        ng = np.asarray(n, np.float64)[g]
        scale = np.maximum(tg[:, :, None] + tg[:, None, :], 1.0)
        both = np.isfinite(rel) & np.isfinite(p_rel)
        if both.any():
            red_gap = _worse(red_gap, float(np.max(
                np.abs(p_red[both] - red[both]) / scale[both])))
            posed = both & (denom >= 0.01 * scale)
            if posed.any():
                rel_gap = _worse(rel_gap, float(np.max(
                    np.abs(p_rel[posed] - rel[posed]))))
        mergeable = ((ng[:, :, None] > 0) & (ng[:, None, :] > 0)
                     & ~np.eye(c, dtype=bool)[None])
        scored = np.isfinite(p_rel)
        wrong += int(np.count_nonzero(scored & ~mergeable)
                     + np.count_nonzero(~scored & mergeable & (denom >= 1.0)))
    return {"merge_gain_red_gap": red_gap, "merge_gain_rel_gap": rel_gap,
            "merge_gain_mask_wrong": wrong}


def control(operands, groups, dtype,
            xp=None) -> tuple[np.ndarray, np.ndarray]:
    """The reference computed in ``dtype`` (bfloat16: the precision below
    the kernel's float32), on the device under ``jax.numpy`` unless ``xp``
    says otherwise, as [G, C, C] answers in the program's place, filled for
    the sampled ``groups`` only."""
    if xp is None:
        import jax.numpy as xp

    m, n, s, t, n_u, cidx, w, cbar, log2v = operands
    g_all, c = np.asarray(n).shape
    rel = np.full((g_all, c, c), -np.inf)
    red = np.zeros((g_all, c, c))
    for lo in range(0, groups.size, BLOCK_GROUPS):
        g = groups[lo:lo + BLOCK_GROUPS]
        r, d, _ = reference(*(np.asarray(x)[g] for x in
                              (m, n, s, t, n_u, cidx, w)),
                            np.asarray(cbar), np.asarray(log2v), xp=xp,
                            dtype=dtype)
        rel[g], red[g] = np.asarray(r, np.float64), np.asarray(d, np.float64)
    return rel, red
