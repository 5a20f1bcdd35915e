"""The graph generators of the benchmark's configurations, each written from
the public definition its configuration names as its source.

**Graph 500**, from the specification's generator (Sect. 3, "Generating the
Edge List", and its sample implementation): ``edgefactor · 2**SCALE``
edges, each of whose ``SCALE`` bit levels picks one quadrant of the
adjacency matrix with probabilities A, B, C, D = 1 − A − B − C; then the
vertex labels are permuted at random.

**LFR** (Lancichinetti, Fortunato and Radicchi, Phys. Rev. E 78, 046110,
2008): degrees from a power law of exponent γ up to ``max_degree`` with the
given mean, community sizes from a power law of exponent β, each node in one
community that can hold its internal degree, a share μ of every node's edges
leaving its community. Stubs are matched at random, inside each community
and then across the graph; the stubs of a self-loop, a repeated edge or an
external edge inside one community are matched again, and those still
unmatched after ``REWIRE_ROUNDS`` rounds are dropped.

SSumM summarizes simple undirected graphs, so every edge list is made
canonical after generation (``src < dst``, self-loops and duplicates
dropped).

:func:`generate` draws the edge structure once from the configuration's
fixed ``structure_seed`` and takes the vertex permutation from the run's
seed: every seed gets the same graph, and so the same work, with its
vertices in another order. Pure numpy; any non-negative integer seed.
"""

from __future__ import annotations

import numpy as np


def canonical(src: np.ndarray, dst: np.ndarray, v: int):
    """``(lo, hi)`` int64, ``lo < hi``, no duplicates, sorted."""
    lo = np.minimum(src, dst).astype(np.int64)
    hi = np.maximum(src, dst).astype(np.int64)
    keep = lo != hi
    key = np.unique(lo[keep] * v + hi[keep])
    return key // v, key % v


def kronecker(rng, scale: int, edgefactor: int, initiator):
    """The specification's edge list before its vertex permutation:
    ``(i, j)`` int64 arrays of ``edgefactor · 2**scale`` edges."""
    a, b, c = (float(x) for x in initiator[:3])
    m = edgefactor << scale
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for bit in range(scale):
        i_bit = rng.random(m) > ab
        j_bit = rng.random(m) > np.where(i_bit, c_norm, a_norm)
        i |= i_bit.astype(np.int64) << bit
        j |= j_bit.astype(np.int64) << bit
    return i, j


#: Times the stubs of rejected pairs are matched again.
REWIRE_ROUNDS = 50


def power_law(rng, size: int, exponent: float, lo: float, hi: float):
    """``size`` samples of the density ∝ x^-exponent on [lo, hi]."""
    u = rng.random(size)
    if exponent == 1.0:
        return lo * (hi / lo) ** u
    a = 1.0 - exponent
    return (lo ** a + u * (hi ** a - lo ** a)) ** (1.0 / a)


def power_law_min(mean: float, exponent: float, hi: float) -> float:
    """The lower end that gives the power law on [lo, hi] its ``mean``."""
    def mean_of(lo):
        if exponent == 2.0:
            return np.log(hi / lo) / (1.0 / lo - 1.0 / hi)
        a, b = 1.0 - exponent, 2.0 - exponent
        return (a / b) * (hi ** b - lo ** b) / (hi ** a - lo ** a)

    lo, up = 1.0, hi
    for _ in range(200):
        mid = 0.5 * (lo + up)
        lo, up = (mid, up) if mean_of(mid) < mean else (lo, mid)
    return 0.5 * (lo + up)


def lfr(rng, n: int, avg_degree: float, max_degree: int, gamma: float,
        beta: float, min_community: int, max_community: int, mixing: float):
    """``(src, dst)`` of an LFR graph before canonicalization, and the
    community of every node."""
    kmin = power_law_min(avg_degree, gamma, max_degree)
    deg = np.rint(power_law(rng, n, gamma, kmin, max_degree)).astype(np.int64)
    k_in = np.rint((1.0 - mixing) * deg).astype(np.int64)
    k_out = deg - k_in
    # community sizes summing to n, none under min_community
    sizes = []
    while sum(sizes) < n:
        sizes.append(int(np.rint(power_law(rng, 1, beta, min_community,
                                           max_community)[0])))
    sizes = np.array(sizes, np.int64)
    while sizes.sum() > n:
        free = np.flatnonzero(sizes > min_community)
        sizes[rng.choice(free)] -= 1
    # most constrained nodes first, each into a random community that can
    # hold its internal degree and has room
    order = np.argsort(-k_in, kind="stable")
    by_size = np.argsort(-sizes, kind="stable")
    room = sizes.copy()
    community = np.empty(n, np.int64)
    open_, nxt = [], 0
    for node in order:
        while nxt < by_size.size and sizes[by_size[nxt]] > k_in[node]:
            open_.append(int(by_size[nxt]))
            nxt += 1
        if not open_:
            raise ValueError("no community can hold node's internal degree")
        j = int(rng.integers(len(open_)))
        c = open_[j]
        community[node] = c
        room[c] -= 1
        if room[c] == 0:
            open_[j] = open_[-1]
            open_.pop()

    def match(stub_node, group):
        """Random pairs of stubs within each group (an odd stub out)."""
        key = np.lexsort((rng.random(stub_node.size), group))
        node, grp = stub_node[key], group[key]
        first = np.flatnonzero(np.r_[True, grp[1:] != grp[:-1]])
        rank = np.arange(node.size) - np.repeat(first, np.diff(
            np.r_[first, node.size]))
        a = np.flatnonzero((rank % 2 == 0)[:-1] & (grp[1:] == grp[:-1]))
        return node[a], node[a + 1]

    def wire(stubs, group, taken):
        """Edges from matching ``stubs`` within their ``group``; the stubs of
        a self-loop, an edge already ``taken`` or drawn twice, or an
        external edge inside one community go back to be matched again."""
        edges = []
        for _ in range(REWIRE_ROUNDS):
            if stubs.size < 2:
                break
            a, b = match(stubs, group)
            lo, hi = np.minimum(a, b), np.maximum(a, b)
            key = lo * n + hi
            _, first = np.unique(key, return_index=True)
            ok = np.zeros(key.size, bool)
            ok[first] = True
            ok &= (lo != hi) & ~np.isin(key, taken)
            if external:
                ok &= community[lo] != community[hi]
            taken = np.union1d(taken, key[ok])
            edges.append(key[ok])
            back = np.concatenate([a[~ok], b[~ok]])
            if back.size == stubs.size:
                break
            stubs, group = back, (np.zeros(back.size, np.int64) if external
                                  else community[back])
        return np.concatenate(edges) if edges else np.zeros(0, np.int64)

    external = False
    stubs = np.repeat(np.arange(n), k_in)
    inner = wire(stubs, community[stubs], np.zeros(0, np.int64))
    external = True
    stubs = np.repeat(np.arange(n), k_out)
    outer = wire(stubs, np.zeros(stubs.size, np.int64), inner)
    key = np.concatenate([inner, outer])
    return key // n, key % n, community


def generate(config: dict, seed: int):
    """``(src int32[E], dst int32[E], V)`` of a configuration: its structure
    from ``structure_seed``, its vertex permutation from ``seed``. The
    canonical edge count has to be the ``edges`` the configuration states."""
    rng = np.random.default_rng(int(config["structure_seed"]))
    kind = config["generator"]
    if kind == "graph500_kronecker":
        v = 1 << int(config["scale"])
        i, j = kronecker(rng, int(config["scale"]), int(config["edgefactor"]),
                         config["initiator"])
    elif kind == "lfr":
        v = int(config["nodes"])
        i, j, _ = lfr(rng, v, float(config["avg_degree"]),
                      int(config["max_degree"]),
                      float(config["degree_exponent"]),
                      float(config["community_exponent"]),
                      int(config["min_community"]),
                      int(config["max_community"]), float(config["mixing"]))
    else:
        raise ValueError(f"unknown graph generator {kind!r}")
    perm = np.random.default_rng(seed).permutation(v)
    lo, hi = canonical(perm[i], perm[j], v)
    if lo.size != int(config["edges"]):
        raise ValueError(f"the generator gave {lo.size} edges; the "
                         f"configuration states {config['edges']}")
    return lo.astype(np.int32), hi.astype(np.int32), v
