"""The plain reference of a summary: Eq. (4) size and Eq. (2) RE₁ recomputed
from the returned partition and superedges, in float64 numpy.

Same semantics as the program's ``core/ref_numpy.py`` and its float32 device
metrics, written again here so that the program cannot move its own
yardstick. The integer part (which supernode pairs have subedges, how many,
which of them the summary keeps) is exact; the floating-point part is
:func:`eq2_eq4`, which runs in any precision: float64 numpy for the
reference, bfloat16 on the device for the control.
"""

from __future__ import annotations

import math

import numpy as np


def pair_table(res, src: np.ndarray, dst: np.ndarray, v: int) -> dict:
    """Supernode pairs of the true graph under ``res``'s partition, which of
    them ``res`` keeps, and the integrity counts of its superedges."""
    n2s = np.asarray(res.node2super, np.int64)
    a, b = n2s[src], n2s[dst]
    keys, cnt = np.unique(np.minimum(a, b) * v + np.maximum(a, b),
                          return_counts=True)
    kept = (np.asarray(res.edge_lo, np.int64) * v
            + np.asarray(res.edge_hi, np.int64))
    pos = np.minimum(np.searchsorted(keys, kept), keys.size - 1)
    real = keys[pos] == kept
    omega = np.asarray(res.edge_w, np.int64)
    keep = np.zeros(keys.size, bool)
    keep[pos[real]] = True
    size = np.asarray(res.super_size, np.int64)
    members = np.bincount(n2s, minlength=v)
    return {
        "keys": keys, "cnt": cnt, "keep": keep, "size": size,
        "num_supernodes": int(np.count_nonzero(members)),
        "num_superedges": int(kept.size),
        "w_max": int(cnt[pos[real]].max()) if real.any() else 0,
        # superedges kept twice, joining no subedges, or with a wrong ω
        "superedges_wrong": int(kept.size - np.unique(kept).size
                                + np.count_nonzero(~real)
                                + np.count_nonzero(omega[real]
                                                   != cnt[pos[real]])),
        # supernodes whose size is not their member count
        "sizes_wrong": int(np.count_nonzero(members != size)),
    }


def eq2_eq4(table: dict, v: int, xp=np, dtype=np.float64) -> dict:
    """Eq. (4) size and Eq. (2) RE₁ from :func:`pair_table`, with every
    floating-point value and sum in ``dtype`` under the array module
    ``xp`` (numpy or jax.numpy)."""
    def f(x):
        return xp.asarray(x, dtype)

    keys = table["keys"]
    size = table["size"]
    plo, phi = keys // v, keys % v
    s_lo, s_hi = f(size[plo]), f(size[phi])
    pi = xp.where(f(plo == phi) > 0, s_lo * (s_lo - f(1)) / f(2), s_lo * s_hi)
    cnt = f(table["cnt"])
    sigma = cnt / xp.maximum(pi, f(1))
    keep = f(table["keep"]) > 0
    err = xp.sum(xp.where(keep, f(2) * cnt * (f(1) - sigma), cnt))
    re1 = f(2) * err / (f(v) * (f(v) - f(1)))
    log_s = xp.log2(f(max(table["num_supernodes"], 2)))
    p = f(table["num_superedges"])
    size_bits = (p * (f(2) * log_s + xp.log2(f(max(table["w_max"], 2))))
                 + f(v) * log_s)
    return {"size_bits": float(size_bits), "re1": float(re1)}


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / abs(want) if want else abs(got)


def compare(res, table: dict, want: dict, v: int, k_bits: float) -> dict:
    """The numbers that decide whether ``res`` is correct, each worse when
    higher. ``want`` is :func:`eq2_eq4` of the reference."""
    return {
        "size_gap": rel_gap(float(res.size_bits), want["size_bits"]),
        "re1_gap": rel_gap(float(res.re1), want["re1"]),
        "budget_used": want["size_bits"] / k_bits,
        "superedges_wrong": table["superedges_wrong"],
        "partition_wrong": (table["sizes_wrong"]
                            + abs(int(res.num_supernodes)
                                  - table["num_supernodes"])
                            + abs(int(res.num_superedges)
                                  - table["num_superedges"])),
        "supernode_share": table["num_supernodes"] / v,
    }


def worst(readings: list[dict]) -> dict:
    """Per number, the worst of several jobs' readings."""
    return {k: max(r[k] for r in readings) for k in readings[0]}


def size_bits_of_graph(v: int, e: int) -> float:
    """Eq. (3), Size(G) = 2|E|log₂|V|: what the budget is a fraction of."""
    return 2.0 * e * math.log2(v)
