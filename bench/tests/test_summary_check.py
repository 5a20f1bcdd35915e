"""The summarization check: the reference agrees with the program on sound
summaries, and the control (the reference in bfloat16 in the program's
place) and planted faults come out as not correct, at a size a test run
can hold."""

import dataclasses

import numpy as np
import pytest

import run as bench_run
from harness import graphs, summary_check

CELLS = ["summarize.graph500-s17", "summarize.lfr"]


@pytest.fixture(scope="module", params=CELLS)
def job(request):
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from repro.core import summarize

    spec = bench_run.load_cell(request.param, rehearse=True)
    driver = bench_run.load_driver(spec["traffic"]["driver"])
    run = driver.Run(spec=spec, seed=5, seconds=0.0, trace=False,
                     rehearse=True, device={}, t_start=0.0, compiles=None)
    src, dst, v = graphs.generate(spec["config"], 5)
    cfg = run.summary_config()
    res = summarize(src, dst, v, cfg, collect_history=False)
    k_bits = cfg.target_bits(summary_check.size_bits_of_graph(v, src.size))
    return spec, res, src, dst, v, k_bits


def readings(res, src, dst, v, k_bits):
    table = summary_check.pair_table(res, src, dst, v)
    want = summary_check.eq2_eq4(table, v)
    return summary_check.compare(res, table, want, v, k_bits), table


def failing(spec, got):
    limits = spec["config"]["limits"]
    return sorted(k for k, lim in limits.items() if k in got and got[k] > lim)


def test_program_passes(job):
    spec, res, src, dst, v, k_bits = job
    got, _ = readings(res, src, dst, v, k_bits)
    assert failing(spec, got) == []
    assert got["superedges_wrong"] == 0 and got["partition_wrong"] == 0
    assert got["size_gap"] < 1e-6 and got["re1_gap"] < 1e-6


def test_reference_matches_dense_evaluation(job):
    """The benchmark's reference gives the values of the program's dense
    float64 evaluation (``core/evaluate.py``, Eq. (1), (2), (4) over the
    reconstructed adjacency) of the same summary."""
    from repro.core import evaluate

    spec, res, src, dst, v, k_bits = job
    table = summary_check.pair_table(res, src, dst, v)
    want = summary_check.eq2_eq4(table, v)
    re1 = evaluate.re_p_dense(evaluate.dense_adjacency(src, dst, v),
                              evaluate.reconstruct_dense(res), 1)
    assert want["re1"] == pytest.approx(re1, rel=1e-9)
    assert want["size_bits"] == pytest.approx(
        evaluate.summary_size_bits_dense(res), rel=1e-12)


def test_bfloat16_control_fails(job):
    import jax.numpy as jnp

    spec, res, src, dst, v, k_bits = job
    table = summary_check.pair_table(res, src, dst, v)
    want = summary_check.eq2_eq4(table, v)
    low = summary_check.eq2_eq4(table, v, xp=jnp, dtype=jnp.bfloat16)
    ctrl = summary_check.compare(
        dataclasses.replace(res, size_bits=low["size_bits"], re1=low["re1"]),
        table, want, v, k_bits)
    assert failing(spec, ctrl) != []


def test_planted_faults_fail(job):
    spec, res, src, dst, v, k_bits = job
    # a superedge's weight altered
    w = res.edge_w.copy()
    w[0] += 1
    got, _ = readings(dataclasses.replace(res, edge_w=w), src, dst, v,
                      k_bits)
    assert "superedges_wrong" in failing(spec, got)
    # a node moved to another supernode without its sizes following
    n2s = res.node2super.copy()
    n2s[0] = n2s[-1] if n2s[-1] != n2s[0] else n2s[1]
    got, _ = readings(dataclasses.replace(res, node2super=n2s), src, dst, v,
                      k_bits)
    assert "partition_wrong" in failing(spec, got)
    # the partition left as it started: every node its own supernode
    ident = dataclasses.replace(
        res, node2super=np.arange(v, dtype=np.int32),
        super_size=np.ones(v, np.int32), num_supernodes=v,
        edge_lo=src, edge_hi=dst, edge_w=np.ones(src.size, np.int64),
        num_superedges=src.size)
    got, _ = readings(ident, src, dst, v, k_bits)
    assert got["supernode_share"] == 1.0
    assert "supernode_share" in failing(spec, got)
