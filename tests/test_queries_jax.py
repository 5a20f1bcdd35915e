"""Differential tests for the batched device query engine (DESIGN.md §14).

Property: for ANY summary graph — hypothesis-driven random partitions +
superedge sets, plus the edge cases the old suite missed (self-loop-only
blocks, dangling blocks, singleton supernodes, empty superedge set,
ξ-dropped summaries) — the batched JAX answers equal the single-query
numpy `repro.core.queries` answers equal the dense-reconstruction ground
truth. PR 10 extends the property to the analytics kinds: cut weight,
conductance, and k-hop size agree with numpy at 1e-9 and with the dense
Â (indicator bilinear forms / support BFS) over random node sets
including empty A, A = all nodes, and k = 0. Count/size-free float
comparisons are pinned far below the documented 1e-6 drift budget (both
paths are float64)."""

import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import SummaryConfig, summarize
from repro.core import evaluate as ev
from repro.core import queries as Q
from repro.core.queries_jax import (
    KIND_ADJACENCY,
    KIND_CONDUCTANCE,
    KIND_CUT,
    KIND_DEGREE,
    KIND_KHOP,
    KIND_PAGERANK,
    KIND_TRIANGLE,
    QueryEngine,
    enable_x64,
    pack_set_counts,
    planned_row_sums,
    row_sum_plans,
)
from repro.core.types import SummaryResult
from repro.graphs import generate


def _make_result(node2super: np.ndarray, pairs: list) -> SummaryResult:
    """A SummaryResult carrying just the summary graph (metrics zeroed)."""
    v = node2super.shape[0]
    size = np.bincount(node2super, minlength=v).astype(np.int32)
    lo = np.array([p[0] for p in pairs], np.int32)
    hi = np.array([p[1] for p in pairs], np.int32)
    w = np.array([p[2] for p in pairs], np.int64)
    return SummaryResult(
        node2super=node2super.astype(np.int32), super_size=size,
        edge_lo=lo, edge_hi=hi, edge_w=w,
        num_supernodes=int(np.unique(node2super).shape[0]),
        num_superedges=len(pairs), size_bits=0.0, input_size_bits=1.0,
        re1=0.0, re2=0.0, mdl_cost=0.0, iterations_run=0)


def _random_summary(rng, v_max: int = 28, edge_frac: float = 0.5):
    """Random partition of [0, V) into supernodes + random valid superedge
    set (weights within pair capacity, zero-capacity self pairs never
    emitted — they have no Π to spread mass over)."""
    v = int(rng.integers(4, v_max))
    s = int(rng.integers(1, v + 1))
    ids = np.sort(rng.choice(v, size=s, replace=False)).astype(np.int32)
    node2super = rng.choice(ids, size=v).astype(np.int32)
    node2super[rng.permutation(v)[:s]] = ids  # every block nonempty
    live = np.unique(node2super)
    n = np.bincount(node2super, minlength=v)[live].astype(np.int64)
    pairs = []
    for i, a in enumerate(live):
        for j in range(i, len(live)):
            b = live[j]
            cap = n[i] * (n[i] - 1) // 2 if a == b else n[i] * n[j]
            if cap > 0 and rng.random() < edge_frac:
                pairs.append((int(a), int(b),
                              int(rng.integers(1, cap + 1))))
    return _make_result(node2super, pairs)


def _dense_pagerank(a: np.ndarray, damping=0.85, iters=100):
    v = a.shape[0]
    deg = a.sum(1)
    p = np.full(v, 1.0 / v)
    for _ in range(iters):
        share = np.where(deg > 0, p / np.maximum(deg, 1e-300), 0.0)
        new = a.T @ share
        dangling = float(p[deg <= 0].sum())
        p = (1 - damping) / v + damping * (new + dangling / v)
    return p


def _assert_differential(res: SummaryResult, check_dense_pagerank=True):
    """Batched JAX == single-query numpy == dense reconstruction."""
    v = res.node2super.shape[0]
    eng = QueryEngine(res)
    a_hat = ev.reconstruct_dense(res)
    rng = np.random.default_rng(0)

    # --- expected degree over every node -------------------------------
    deg_jax = eng.expected_degree(np.arange(v))
    deg_np = np.array([Q.expected_degree(res, u) for u in range(v)])
    np.testing.assert_allclose(deg_jax, deg_np, rtol=1e-12, atol=1e-15)
    np.testing.assert_allclose(deg_np, a_hat.sum(1), rtol=1e-9, atol=1e-12)

    # --- adjacency: random pairs + diagonal + same-block pairs ---------
    u = np.concatenate([rng.integers(0, v, 40), np.arange(v)[:8]])
    w = np.concatenate([rng.integers(0, v, 40), np.arange(v)[:8]])
    adj_jax = eng.adjacency_weight(u.astype(np.int32), w.astype(np.int32))
    adj_np = np.array([Q.adjacency_weight(res, a, b) for a, b in zip(u, w)])
    np.testing.assert_allclose(adj_jax, adj_np, rtol=1e-12, atol=1e-15)
    np.testing.assert_allclose(adj_np, a_hat[u, w], rtol=1e-9, atol=1e-12)

    # --- PageRank ------------------------------------------------------
    pr_jax = eng.pagerank_nodes(np.arange(v))
    pr_np = Q.pagerank_summary(res)
    np.testing.assert_allclose(pr_jax, pr_np, rtol=1e-9, atol=1e-12)
    if check_dense_pagerank:
        eng100 = QueryEngine(res, pagerank_iters=100)
        np.testing.assert_allclose(
            eng100.pagerank_nodes(np.arange(v)), _dense_pagerank(a_hat),
            rtol=5e-4, atol=1e-9)

    # --- triangle density ---------------------------------------------
    tri_jax = eng.triangle_density()
    tri_np = Q.triangle_density(res)
    np.testing.assert_allclose(tri_jax, tri_np, rtol=1e-9, atol=1e-12)
    if not np.any(res.edge_lo == res.edge_hi):
        # without self-superedges, the block-triple formula is exactly the
        # dense E[#triangles] = tr(Â³)/6
        tri_dense = float(np.trace(a_hat @ a_hat @ a_hat) / 6.0)
        np.testing.assert_allclose(tri_np, tri_dense, rtol=1e-8, atol=1e-9)

    # --- cut weight: random pairs + empty A + A = everything -----------
    sets_a = [rng.choice(v, size=int(rng.integers(0, v + 1)),
                         replace=False) for _ in range(6)]
    sets_b = [rng.choice(v, size=int(rng.integers(0, v + 1)),
                         replace=False) for _ in range(6)]
    sets_a += [np.array([], np.int64), np.arange(v)]
    sets_b += [rng.choice(v, size=max(1, v // 2), replace=False),
               np.arange(v)]
    cut_jax = eng.cut_weight(sets_a, sets_b)
    cut_np = np.array([Q.cut_weight(res, a, b)
                       for a, b in zip(sets_a, sets_b)])
    np.testing.assert_allclose(cut_jax, cut_np, rtol=0, atol=1e-9)
    for got, a, b in zip(cut_np, sets_a, sets_b):
        ia = np.zeros(v)
        ia[np.asarray(a, np.int64)] = 1.0
        ib = np.zeros(v)
        ib[np.asarray(b, np.int64)] = 1.0
        np.testing.assert_allclose(got, ia @ a_hat @ ib,
                                   rtol=1e-9, atol=1e-9)

    # --- conductance: same sets (incl. empty and full A -> 0) ----------
    cond_jax = eng.conductance(sets_a)
    cond_np = np.array([Q.conductance(res, a) for a in sets_a])
    np.testing.assert_allclose(cond_jax, cond_np, rtol=0, atol=1e-9)
    for got, a in zip(cond_np, sets_a):
        ia = np.zeros(v)
        ia[np.asarray(a, np.int64)] = 1.0
        dense_cut = ia @ a_hat @ (1.0 - ia)
        denom = min(float(ia @ a_hat.sum(1)),
                    float((1.0 - ia) @ a_hat.sum(1)))
        want = dense_cut / denom if denom > 0 else 0.0
        np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)
    assert cond_np[-2] == 0.0 and cond_np[-1] == 0.0  # empty / full A

    # --- k-hop size: k = 0 through k = khop_max vs dense BFS -----------
    # (k is capped at the engine's khop_max BFS budget: below it the
    # jitted fixpoint loop runs exactly k steps like the numpy reference)
    ku = rng.integers(0, v, 10).astype(np.int64)
    kk = np.concatenate([[0, 0], rng.integers(1, 5, 6),
                         [eng.khop_max, eng.khop_max]])
    khop_jax = eng.k_hop_size(ku, kk[:10])
    khop_np = np.array([Q.k_hop_size(res, int(a), int(k))
                        for a, k in zip(ku, kk)])
    np.testing.assert_allclose(khop_jax, khop_np, rtol=0, atol=1e-9)
    support = a_hat > 0
    for got, a, k in zip(khop_np, ku, kk):
        reach = np.zeros(v, bool)
        reach[a] = True
        for _ in range(min(int(k), v)):
            reach = reach | (support @ reach)
        np.testing.assert_allclose(got, float(reach.sum()),
                                   rtol=1e-9, atol=1e-9)
    assert np.all(khop_np[:2] == 1.0)  # k = 0 is just the node itself

    # --- fused mixed-kind batch == the per-kind kernels ----------------
    b = 16
    kinds = np.array([KIND_DEGREE, KIND_ADJACENCY, KIND_PAGERANK,
                      KIND_TRIANGLE, KIND_KHOP, KIND_CUT,
                      KIND_CONDUCTANCE, KIND_DEGREE] * (b // 8), np.int32)
    bu = rng.integers(0, v, b).astype(np.int32)
    bv = rng.integers(0, v, b).astype(np.int32)
    bv[kinds == KIND_KHOP] = rng.integers(0, 4, (kinds == KIND_KHOP).sum())
    bsets_a = [rng.choice(v, size=int(rng.integers(0, v + 1)),
                          replace=False) for _ in range(b)]
    bsets_b = [rng.choice(v, size=int(rng.integers(0, v + 1)),
                          replace=False) for _ in range(b)]
    ca, cb, ov = pack_set_counts(eng.bs, kinds, bsets_a, bsets_b)
    ans = eng.answer_batch(kinds, bu, bv, ca, cb, ov)
    for s in range(b):
        if kinds[s] == KIND_DEGREE:
            want = deg_np[bu[s]]
        elif kinds[s] == KIND_ADJACENCY:
            want = Q.adjacency_weight(res, bu[s], bv[s])
        elif kinds[s] == KIND_PAGERANK:
            want = pr_np[bu[s]]
        elif kinds[s] == KIND_KHOP:
            want = Q.k_hop_size(res, int(bu[s]), int(bv[s]))
        elif kinds[s] == KIND_CUT:
            want = Q.cut_weight(res, bsets_a[s], bsets_b[s])
        elif kinds[s] == KIND_CONDUCTANCE:
            want = Q.conductance(res, bsets_a[s])
        else:
            want = tri_np
        np.testing.assert_allclose(ans[s], want, rtol=1e-9, atol=1e-12)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_differential_random_summaries(seed):
    rng = np.random.default_rng(seed)
    _assert_differential(_random_summary(rng),
                         check_dense_pagerank=(seed % 3 == 0))


def test_empty_superedge_set():
    """ξ dropped everything / no edges survived: all queries are defined
    (degree 0, uniform PageRank, zero triangles)."""
    rng = np.random.default_rng(7)
    res = _random_summary(rng, edge_frac=0.0)
    assert res.num_superedges == 0
    _assert_differential(res)
    eng = QueryEngine(res)
    v = res.node2super.shape[0]
    assert np.all(eng.expected_degree(np.arange(v)) == 0.0)
    np.testing.assert_allclose(eng.pagerank_nodes(np.arange(v)), 1.0 / v)
    assert eng.triangle_density() == 0.0


def test_self_loop_only_blocks():
    """Blocks whose only superedge is their self-loop (plus a singleton
    block, whose zero-capacity self pair must never materialize)."""
    node2super = np.array([0, 0, 0, 3, 3, 5], np.int32)
    res = _make_result(node2super, [(0, 0, 3), (3, 3, 1)])
    _assert_differential(res)
    eng = QueryEngine(res)
    # block {0,1,2}: σ = 3/C(3,2) = 1 → expected degree 2 (clique)
    np.testing.assert_allclose(eng.expected_degree(np.array([0])), [2.0])
    # singleton block 5 is dangling
    np.testing.assert_allclose(eng.expected_degree(np.array([5])), [0.0])


def test_dangling_and_singleton_blocks():
    """Dangling blocks redistribute PageRank mass uniformly; singleton
    supernodes answer adjacency through their cross σ only."""
    node2super = np.array([0, 0, 2, 3, 3, 3, 6], np.int32)
    res = _make_result(node2super, [(0, 2, 1), (2, 3, 2)])  # 6 dangling
    _assert_differential(res)
    assert Q.expected_degree(res, 6) == 0.0
    assert Q.adjacency_weight(res, 2, 6) == 0.0
    # singleton block 2 ↔ pair block {0,1}: σ = 1/2
    np.testing.assert_allclose(Q.adjacency_weight(res, 0, 2), 0.5)


def test_xi_dropped_real_summary():
    """A real SSumM run at an aggressive budget (further sparsification
    drops superedges) still satisfies the differential property."""
    src, dst, v = generate("ego-facebook", seed=3, scale=0.04)
    res = summarize(src, dst, v, SummaryConfig(T=6, k_frac=0.15, seed=3),
                    collect_history=False)
    assert res.num_supernodes > 1
    _assert_differential(res, check_dense_pagerank=False)


def test_real_summary_differential():
    src, dst, v = generate("ego-facebook", seed=2, scale=0.05)
    res = summarize(src, dst, v, SummaryConfig(T=6, k_frac=0.4, seed=2),
                    collect_history=False)
    _assert_differential(res)


def test_block_build_memoized():
    """Regression (ISSUE 8): two successive queries must not rebuild the
    O(|P|) block-space CSR — the build is memoized per SummaryResult."""
    rng = np.random.default_rng(11)
    res = _random_summary(rng)
    fresh = dataclasses.replace(res)  # drops the memo cache attribute
    before = Q.BLOCK_BUILDS
    Q.expected_degree(fresh, 0)
    Q.pagerank_summary(fresh)
    Q.triangle_density(fresh)
    Q.adjacency_weight(fresh, 0, 1)
    assert Q.BLOCK_BUILDS == before + 1
    # a distinct result object builds its own
    Q.expected_degree(dataclasses.replace(res), 0)
    assert Q.BLOCK_BUILDS == before + 2


def test_device_engine_reuses_host_memo():
    rng = np.random.default_rng(13)
    res = _random_summary(rng)
    fresh = dataclasses.replace(res)
    before = Q.BLOCK_BUILDS
    QueryEngine(fresh)
    QueryEngine(fresh)
    Q.expected_degree(fresh, 0)
    assert Q.BLOCK_BUILDS == before + 1


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_engine_accepts_plain_python_and_numpy_targets(dtype):
    rng = np.random.default_rng(5)
    res = _random_summary(rng)
    eng = QueryEngine(res)
    v = res.node2super.shape[0]
    one = eng.expected_degree(np.asarray([v - 1], dtype))
    assert one.shape == (1,) and one.dtype == np.float64


@pytest.mark.parametrize("lengths", [[0], [1, 0, 3], [4, 5, 16, 17, 0, 2],
                                     list(range(40))])
def test_row_sum_plan_sums_each_row_identically(lengths):
    """Planned per-row sums agree with numpy, and split over two devices'
    stacked plans every row gets the same bits as in the one-device plan
    — the property the partitioned tier's bit-identity rests on."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(len(lengths))
    lengths = np.asarray(lengths)
    starts = np.cumsum(lengths) - lengths
    vals = rng.random(int(lengths.sum()))
    want = np.array([vals[a:a + n].sum() for a, n in zip(starts, lengths)])
    def plan_of(stacked, q):
        return jax.tree_util.tree_map(lambda x: jnp.asarray(x[q]), stacked)

    with enable_x64():
        one = np.asarray(planned_row_sums(plan_of(row_sum_plans([lengths]), 0),
                                          jnp.asarray(vals)))
        np.testing.assert_allclose(one, want, rtol=1e-12)
        rows = [np.arange(0, lengths.size, 2), np.arange(1, lengths.size, 2)]
        width = max(r.size for r in rows)
        dev_len = np.zeros((2, width), np.int64)
        dev_vals = np.zeros((2, max(1, int(lengths.sum()))))
        for q, r in enumerate(rows):
            dev_len[q, :r.size] = lengths[r]
            mine = np.concatenate(
                [vals[starts[i]:starts[i] + lengths[i]] for i in r] + [[]])
            dev_vals[q, :mine.size] = mine
        stacked = row_sum_plans(dev_len)
        for q, r in enumerate(rows):
            got = np.asarray(planned_row_sums(plan_of(stacked, q),
                                              jnp.asarray(dev_vals[q])))
            np.testing.assert_array_equal(got[:r.size], one[r])
