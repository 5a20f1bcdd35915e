import numpy as np
import pytest

from harness import graphs

SPEC = {"generator": "graph500_kronecker", "structure_seed": 0, "scale": 11,
        "edgefactor": 16, "initiator": [0.57, 0.19, 0.19, 0.05],
        "edges": 22728}
LFR = {"generator": "lfr", "structure_seed": 0, "nodes": 2048,
       "avg_degree": 20, "max_degree": 50, "degree_exponent": 2.0,
       "community_exponent": 1.0, "min_community": 20, "max_community": 100,
       "mixing": 0.3, "edges": 20090}


@pytest.mark.parametrize("spec", [SPEC, LFR], ids=lambda s: s["generator"])
@pytest.mark.parametrize("seed", [0, 2 ** 31 + 12345, 2 ** 40 + 7])
def test_canonical_and_seeded(spec, seed):
    src, dst, v = graphs.generate(spec, seed)
    assert v == spec.get("nodes", 2 ** spec.get("scale", 0))
    assert src.size == spec["edges"]
    assert src.dtype == np.int32 and dst.dtype == np.int32
    assert (src < dst).all() and (dst < v).all() and (src >= 0).all()
    key = src.astype(np.int64) * v + dst
    assert (np.diff(key) > 0).all()  # sorted, so no duplicates
    again = graphs.generate(spec, seed)
    assert np.array_equal(src, again[0]) and np.array_equal(dst, again[1])
    other = graphs.generate(spec, seed + 1)
    assert not np.array_equal(src, other[0])
    # the same graph, relabelled: equal degree sequences
    deg = np.sort(np.bincount(np.concatenate([src, dst]), minlength=v))
    odeg = np.sort(np.bincount(np.concatenate(other[:2]), minlength=v))
    assert np.array_equal(deg, odeg)


def test_kronecker_quadrants():
    """Each bit level puts an edge in quadrant A, B, C, D with the
    specification's probabilities, and makes edgefactor · 2**scale edges."""
    scale, a, b, c = 4, 0.57, 0.19, 0.19
    i, j = graphs.kronecker(np.random.default_rng(1), scale, 16,
                            [a, b, c, 1 - a - b - c])
    assert i.size == j.size == 16 << scale
    assert i.max() < 2 ** scale and j.max() < 2 ** scale
    i, j = graphs.kronecker(np.random.default_rng(2), 1, 200_000,
                            [a, b, c, 1 - a - b - c])
    got = [np.mean((i == x) & (j == y)) for x, y in
           ((0, 0), (0, 1), (1, 0), (1, 1))]
    assert got == pytest.approx([a, b, c, 1 - a - b - c], abs=3e-3)


def test_edge_count_is_held():
    with pytest.raises(ValueError, match="edges"):
        graphs.generate(dict(SPEC, edges=SPEC["edges"] + 1), 0)
    with pytest.raises(ValueError, match="generator"):
        graphs.generate(dict(SPEC, generator="barabasi_albert"), 0)


def test_lfr_keeps_its_parameters():
    """Degrees up to max_degree with about the mean asked for, communities
    within their size range, and about the share mu of edges leaving their
    community."""
    n = 8192
    lo, hi, com = graphs.lfr(np.random.default_rng(4), n, 20, 50, 2.0, 1.0,
                             20, 100, 0.3)
    assert (lo < hi).all() and np.unique(lo * n + hi).size == lo.size
    deg = np.bincount(np.concatenate([lo, hi]), minlength=n)
    assert deg.max() <= 50 and deg.mean() == pytest.approx(20, rel=0.05)
    sizes = np.bincount(com)
    assert sizes.sum() == n and 20 <= sizes.min() and sizes.max() <= 100
    assert np.mean(com[lo] != com[hi]) == pytest.approx(0.3, abs=0.03)


def test_power_law_mean():
    kmin = graphs.power_law_min(20.0, 2.0, 50.0)
    x = graphs.power_law(np.random.default_rng(0), 200_000, 2.0, kmin, 50.0)
    assert x.min() >= kmin and x.max() <= 50.0
    assert x.mean() == pytest.approx(20.0, rel=0.01)
