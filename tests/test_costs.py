"""Property tests (hypothesis) for the MDL cost machinery + exactness of the
closed-form evaluation against dense brute force (Eqs. 2/4/9/10/11)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import costs
from repro.core.ref_numpy import SSumMRef, _entropy_bits
from repro.core.types import PairTable, SummaryState, init_state, make_graph
from repro.core import evaluate as ev
from repro.core.sparsify import further_sparsify
from repro.core.tables import build_neighbor_tables
from repro.utils import boundaries_from_keys, segment_ids_from_boundaries

SETTINGS = dict(max_examples=30, deadline=None)


# ---------------------------------------------------------------------------
# entropy / encoding properties
# ---------------------------------------------------------------------------


@given(cnt=st.integers(0, 1000), pi=st.integers(0, 1000))
@settings(**SETTINGS)
def test_entropy_bits_bounds(cnt, pi):
    """0 ≤ Cost₍₁₎−C̄ ≤ |Π| bits (entropy of a Bernoulli ≤ 1 bit/slot)."""
    got = float(costs.entropy_bits(jnp.float32(cnt), jnp.float32(pi)))
    assert got >= 0.0
    assert got <= max(pi, 0) + 1e-3
    if 0 < cnt < pi:
        want = _entropy_bits(cnt, pi)
        assert math.isclose(got, want, rel_tol=1e-5, abs_tol=1e-3)
    else:
        assert got == 0.0


@given(cnt=st.integers(1, 500), extra=st.integers(0, 500),
       cbar=st.floats(1.0, 100.0), log2v=st.floats(2.0, 30.0))
@settings(**SETTINGS)
def test_pair_cost_star_is_min(cnt, extra, cbar, log2v):
    pi = cnt + extra
    c1 = cbar + float(costs.entropy_bits(jnp.float32(cnt), jnp.float32(pi)))
    c2 = 2.0 * cnt * log2v
    got = float(costs.pair_cost_star(jnp.float32(cnt), jnp.float32(pi),
                                     jnp.float32(cbar), jnp.float32(log2v)))
    assert math.isclose(got, min(c1, c2), rel_tol=1e-5, abs_tol=1e-3)


@given(st.data())
@settings(**SETTINGS)
def test_keep_decision_consistent_with_costs(data):
    cnt = data.draw(st.integers(1, 200))
    pi = cnt + data.draw(st.integers(0, 400))
    cbar = data.draw(st.floats(1.0, 80.0))
    log2v = data.draw(st.floats(2.0, 24.0))
    keep = bool(costs.keep_superedge(jnp.float32(cnt), jnp.float32(pi),
                                     jnp.float32(cbar), jnp.float32(log2v),
                                     re_guard=0))
    c1 = cbar + float(costs.entropy_bits(jnp.float32(cnt), jnp.float32(pi)))
    c2 = 2.0 * cnt * log2v
    assert keep == (c1 < c2)


# ---------------------------------------------------------------------------
# closed-form evaluation == dense brute force
# ---------------------------------------------------------------------------


def _random_graph_and_partition(rng, v, e_target, n_groups):
    src = rng.integers(0, v, e_target)
    dst = rng.integers(0, v, e_target)
    keep = src != dst
    graph, _ = make_graph(src[keep], dst[keep], v)
    n2s_group = rng.integers(0, n_groups, v)
    # canonical representative ids (supernode id = min member id)
    reps = np.full(n_groups, -1, np.int64)
    n2s = np.zeros(v, np.int64)
    for u in range(v):
        g = n2s_group[u]
        if reps[g] < 0:
            reps[g] = u
        n2s[u] = reps[g]
    return graph, n2s


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_metrics_match_dense_bruteforce(seed):
    rng = np.random.default_rng(seed)
    v = 40
    graph, n2s = _random_graph_and_partition(rng, v, 160, 12)
    e = graph.num_edges
    size = np.bincount(n2s, minlength=v)
    state = SummaryState(
        node2super=jnp.asarray(n2s, jnp.int32),
        size=jnp.asarray(size, jnp.int32),
        rng=jnp.zeros((2,), jnp.uint32),
        t=jnp.asarray(1, jnp.int32),
    )
    pt = costs.build_pair_table(graph.src, graph.dst, state)
    m = costs.summary_metrics(pt, state, v, e, cbar_mode="paper", re_guard=1)

    # --- dense reconstruction with the same keep decisions ---------------
    keep = np.asarray(m["keep"])
    lo = np.asarray(pt.lo)[keep]
    hi = np.asarray(pt.hi)[keep]
    w = np.asarray(pt.cnt)[keep].astype(np.int64)
    from repro.core.types import SummaryResult

    res = SummaryResult(
        node2super=n2s.astype(np.int32), super_size=size.astype(np.int32),
        edge_lo=lo, edge_hi=hi, edge_w=w,
        num_supernodes=int((size > 0).sum()), num_superedges=len(w),
        size_bits=0.0, input_size_bits=0.0, re1=0.0, re2=0.0, mdl_cost=0.0,
        iterations_run=0,
    )
    a = ev.dense_adjacency(np.asarray(graph.src), np.asarray(graph.dst), v)
    a_hat = ev.reconstruct_dense(res)
    np.testing.assert_allclose(float(m["re1"]), ev.re_p_dense(a, a_hat, 1),
                               rtol=1e-4, atol=1e-8)
    np.testing.assert_allclose(float(m["re2"]), ev.re_p_dense(a, a_hat, 2),
                               rtol=1e-4, atol=1e-8)
    np.testing.assert_allclose(float(m["size_bits"]),
                               ev.summary_size_bits_dense(res), rtol=1e-5)


# ---------------------------------------------------------------------------
# Lemma 3.1 (2-hop merger bound) on the sequential oracle's exact costs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lemma_31_reduction_bound(seed):
    rng = np.random.default_rng(seed)
    v = 24
    src = rng.integers(0, v, 60)
    dst = rng.integers(0, v, 60)
    keep = src != dst
    ref = SSumMRef(src[keep], dst[keep], v, cbar_mode="paper", re_guard=0)
    cbar = ref._cbar()
    checked = 0
    for a in range(v):
        for b in ref.adj[a]:  # 1-hop pairs are within 2 hops
            if a >= b:
                continue
            cost_a = ref.supernode_cost(a, cbar)
            cost_b = ref.supernode_cost(b, cbar)
            cost_ab = ref.pair_cost(float(ref.adj[a].get(b, 0)),
                                    ref._pi(a, b), cbar)
            reduction = (cost_a + cost_b - cost_ab) - ref.merged_cost(a, b, cbar)
            assert reduction <= min(cost_a, cost_b) + 1e-6
            checked += 1
    assert checked > 0


@pytest.mark.parametrize("seed", [0, 1])
def test_lemma_32_far_pairs_bound(seed):
    """Mergers of ≥3-hop-apart supernodes reduce cost by ≤ C̄ (Lemma 3.2)."""
    rng = np.random.default_rng(seed)
    v = 30
    # two disconnected cliques => cross pairs are infinitely far apart
    edges = []
    for base in (0, 15):
        for i in range(base, base + 8):
            for j in range(i + 1, base + 8):
                if rng.random() < 0.6:
                    edges.append((i, j))
    src, dst = np.array([e[0] for e in edges]), np.array([e[1] for e in edges])
    ref = SSumMRef(src, dst, v, cbar_mode="paper", re_guard=0)
    cbar = ref._cbar()
    cbar_bound = 2 * ref.log2v + ref.log2e
    for a in range(0, 8):
        for b in range(15, 23):
            cost_a = ref.supernode_cost(a, cbar)
            cost_b = ref.supernode_cost(b, cbar)
            reduction = (cost_a + cost_b) - ref.merged_cost(a, b, cbar)
            assert reduction <= cbar_bound + 1e-6


# ---------------------------------------------------------------------------
# pair table layout: the sorted edge list, each pair marked at its run's start
# ---------------------------------------------------------------------------


def _state_of(n2s, v):
    n2s = np.asarray(n2s, np.int64)
    return SummaryState(
        node2super=jnp.asarray(n2s, jnp.int32),
        size=jnp.asarray(np.bincount(n2s, minlength=v), jnp.int32),
        rng=jnp.zeros((2,), jnp.uint32),
        t=jnp.asarray(1, jnp.int32),
    )


LAYOUT_CASES = ["identity", "one_supernode", "self_loops", "one_edge",
                "random0", "random1", "random2"]


def _layout_case(name):
    """(src, dst, node2super, V) of one case."""
    rng = np.random.default_rng(LAYOUT_CASES.index(name))
    if name == "self_loops":  # raw edges, some with src == dst
        v = 30
        src = rng.integers(0, v, 120)
        dst = np.where(rng.random(120) < 0.3, src, rng.integers(0, v, 120))
        return src, dst, rng.integers(0, 8, v), v
    if name == "one_edge":
        return np.array([3]), np.array([7]), np.arange(10), 10
    v = 50
    graph, n2s = _random_graph_and_partition(rng, v, 250, 12)
    if name == "identity":
        n2s = np.arange(v)
    elif name == "one_supernode":
        n2s = np.zeros(v, np.int64)
    return np.asarray(graph.src), np.asarray(graph.dst), n2s, v


@pytest.mark.parametrize("case", LAYOUT_CASES)
def test_pair_table_layout(case):
    """Valid rows are exactly the distinct (lo, hi) pairs with their counts,
    in ascending order; invalid rows count 0; every id is a supernode id."""
    src, dst, n2s, v = _layout_case(case)
    pt = costs.build_pair_table(jnp.asarray(src, jnp.int32),
                                jnp.asarray(dst, jnp.int32), _state_of(n2s, v))
    lo, hi = np.asarray(pt.lo), np.asarray(pt.hi)
    cnt, valid = np.asarray(pt.cnt), np.asarray(pt.valid)
    su, sv = n2s[src], n2s[dst]
    pairs, counts = np.unique(np.stack([np.minimum(su, sv), np.maximum(su, sv)],
                                       axis=1), axis=0, return_counts=True)
    np.testing.assert_array_equal(np.stack([lo, hi], axis=1)[valid], pairs)
    np.testing.assert_array_equal(cnt[valid], counts.astype(np.float32))
    assert (cnt[~valid] == 0).all()
    assert lo.shape == (len(src),)
    assert ((lo >= 0) & (lo <= hi) & (hi < v)).all()


def _compacted_pair_table(src, dst, state):
    """The pair table as scatters compact it: the runs of the sorted edge
    list written to a prefix of the rows, invalid rows 0."""
    e = src.shape[0]
    su = state.node2super[src]
    sv = state.node2super[dst]
    lo_s, hi_s = jax.lax.sort((jnp.minimum(su, sv), jnp.maximum(su, sv)),
                              num_keys=2)
    pid = segment_ids_from_boundaries(boundaries_from_keys(lo_s, hi_s))
    cnt = jax.ops.segment_sum(jnp.ones((e,), jnp.float32), pid, num_segments=e)
    plo = jnp.zeros((e,), jnp.int32).at[pid].max(lo_s)
    phi = jnp.zeros((e,), jnp.int32).at[pid].max(hi_s)
    valid = jnp.arange(e, dtype=jnp.int32) < pid[-1] + 1
    return PairTable(lo=plo, hi=phi, cnt=jnp.where(valid, cnt, 0.0),
                     valid=valid)


def _by_pair(pt, values):
    """{(lo, hi): value} over the valid rows."""
    valid = np.asarray(pt.valid)
    return {(int(a), int(b)): x.item() for a, b, x in zip(
        np.asarray(pt.lo)[valid], np.asarray(pt.hi)[valid],
        np.asarray(values)[valid])}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pair_table_matches_compacted_form(seed):
    """Every reader of the pair table gives the compacted form's answer:
    the metrics' counts and keep set, the neighbour tables, Cost*_A and the
    drop set, bit for bit."""
    rng = np.random.default_rng(100 + seed)
    v = 60
    graph, n2s = _random_graph_and_partition(rng, v, 400, 14)
    e = graph.num_edges
    state = _state_of(n2s, v)
    pt = costs.build_pair_table(graph.src, graph.dst, state)
    ref = _compacted_pair_table(graph.src, graph.dst, state)

    m, m_ref = (costs.summary_metrics(p, state, v, e) for p in (pt, ref))
    for k in ("num_supernodes", "num_superedges", "omega_max", "size_bits",
              "cbar", "membership_bits"):
        assert float(m[k]) == float(m_ref[k]), k
    for k in ("mdl_cost", "re1", "re2"):  # float sums over moved summands
        np.testing.assert_allclose(float(m[k]), float(m_ref[k]), rtol=1e-6)
    assert _by_pair(pt, m["keep"]) == _by_pair(ref, m_ref["keep"])

    for got, want in zip(build_neighbor_tables(pt, v, 8),
                         build_neighbor_tables(ref, v, 8)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    log2v = jnp.log2(jnp.float32(v))
    t, t_ref = (costs.supernode_total_costs(
        p, costs.pair_pi(p, state.size), mm["cbar"], log2v, v)
        for p, mm in ((pt, m), (ref, m_ref)))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(t_ref))

    k_bits = 0.7 * float(m["size_bits"])
    drop, after = further_sparsify(pt, state, v, e, k_bits)
    drop_ref, after_ref = further_sparsify(ref, state, v, e, k_bits)
    assert np.asarray(drop).sum() > 0
    assert _by_pair(pt, drop) == _by_pair(ref, drop_ref)
    assert float(after["size_bits"]) == float(after_ref["size_bits"])


def test_summarize_matches_compacted_pair_table(monkeypatch):
    """A whole job returns the same partition and superedges whether the
    pair table is kept in sorted order or compacted by scatters."""
    from repro.core import SummaryConfig, summarize
    from repro.graphs import generate

    src, dst, v = generate("ego-facebook", seed=2, scale=0.04)
    cfg = SummaryConfig(T=6, k_frac=0.3, seed=5)
    jax.clear_caches()
    got = summarize(src, dst, v, cfg)
    monkeypatch.setattr(costs, "build_pair_table", _compacted_pair_table)
    jax.clear_caches()
    want = summarize(src, dst, v, cfg)
    jax.clear_caches()
    for k in ("node2super", "super_size", "edge_lo", "edge_hi", "edge_w"):
        np.testing.assert_array_equal(getattr(got, k), getattr(want, k), k)
    assert got.size_bits == want.size_bits
    assert got.iterations_run == want.iterations_run
