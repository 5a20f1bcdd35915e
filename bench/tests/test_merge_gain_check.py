"""The merge-gain check: the float64 reference agrees with the program's
kernel on the operands of a round, and the control (the reference in
bfloat16 in the kernel's place) and planted faults break the limits, at a
size a test run can hold."""

import numpy as np
import pytest

import run as bench_run
from harness import graphs, merge_gain_check

CELLS = ["summarize.graph500-s17", "summarize.lfr"]


@pytest.fixture(scope="module", params=CELLS)
def operands(request):
    """A cell's limits and the merge-gain operands of a round over the
    final partition of one job at the rehearsal size, with the program's
    answers and a sample of groups."""
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from repro.core import summarize
    from repro.core.engine import LocalBackend
    from repro.kernels import ops as kops

    spec = bench_run.load_cell(request.param, rehearse=True)
    driver = bench_run.load_driver(spec["traffic"]["driver"])
    run = driver.Run(spec=spec, seed=11, seconds=0.0, trace=False,
                     rehearse=True, device={}, t_start=0.0, compiles=None)
    src, dst, v = graphs.generate(spec["config"], 11)
    cfg = run.summary_config()
    backend = LocalBackend(src, dst, v, cfg)
    res = summarize(src, dst, v, cfg, collect_history=False)
    args = run.merge_gain_operands(backend, res)
    rel, red = (np.asarray(x) for x in kops.merge_gain(*args))
    ops = tuple(np.asarray(x) for x in args)
    groups = merge_gain_check.sample_groups(ops[1], 11)
    return spec["config"]["limits"], ops, rel, red, groups


def failing(limits, got):
    return sorted(k for k, v in got.items() if v > limits[k])


def test_program_passes(operands):
    limits, ops, rel, red, groups = operands
    assert groups.size >= 8
    got = merge_gain_check.readings(rel, red, ops, groups)
    assert failing(limits, got) == []
    assert got["merge_gain_red_gap"] < 1e-5
    # the partition is not the initial one: sizes above 1 reach the kernel
    assert ops[1].max() > 1


def test_reference_in_float32_agrees(operands):
    """The reference in float32 reads as the kernel does: what separates
    the control is the precision, not the formula."""
    import jax.numpy as jnp

    limits, ops, _, _, groups = operands
    rel, red = merge_gain_check.control(ops, groups, jnp.float32)
    got = merge_gain_check.readings(rel, red, ops, groups)
    assert failing(limits, got) == []


def test_bfloat16_control_fails(operands):
    import jax.numpy as jnp

    limits, ops, _, _, groups = operands
    rel, red = merge_gain_check.control(ops, groups, jnp.bfloat16)
    got = merge_gain_check.readings(rel, red, ops, groups)
    assert {"merge_gain_red_gap", "merge_gain_rel_gap"} & set(
        failing(limits, got))


def test_planted_faults_fail(operands):
    limits, ops, rel, red, groups = operands
    g = groups[0]
    i, j = np.argwhere(np.isfinite(rel[g]))[0]
    # one pair's Reduction off by a tenth of its scale
    bad = red.copy()
    bad[g, i, j] += 0.1 * max(ops[3][g, i] + ops[3][g, j], 1.0)
    got = merge_gain_check.readings(rel, bad, ops, groups)
    assert "merge_gain_red_gap" in failing(limits, got)
    # a member scored against itself
    bad = rel.copy()
    bad[g, i, i] = 0.5
    got = merge_gain_check.readings(bad, red, ops, groups)
    assert "merge_gain_mask_wrong" in failing(limits, got)
    # a NaN is worse than any limit
    bad = rel.copy()
    bad[g, i, j] = np.nan
    got = merge_gain_check.readings(bad, red, ops, groups)
    assert "merge_gain_mask_wrong" in failing(limits, got) or \
        got["merge_gain_rel_gap"] != got["merge_gain_rel_gap"]


def test_sample_is_drawn_from_the_seed(operands):
    _, ops, _, _, _ = operands
    a = merge_gain_check.sample_groups(ops[1], 3, count=8)
    assert np.array_equal(a, merge_gain_check.sample_groups(ops[1], 3,
                                                            count=8))
    assert ((ops[1][a] > 0).sum(axis=1) >= 2).all()
