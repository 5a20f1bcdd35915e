"""A whole run of each cell on the CPU at its rehearsal size, past the
harness's look for a chip, and the same run with the timed path broken
underneath: ``correct`` has to come out false."""

import json

import jax
import numpy as np
import pytest

import run as bench_run

CELLS = ["summarize.graph500-s17", "summarize.lfr"]


def run_cell(capsys, cell, seed, trace=0):
    jax.clear_caches()  # drop programs traced before a patch
    rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", "0.5", "--trace", str(trace),
                         "--rehearse"])
    out, err = capsys.readouterr()
    assert rc == 0
    result = json.loads(out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check complete")
    return result


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct_and_reports_no_metric(capsys, cell):
    result = run_cell(capsys, cell, 2 ** 31 + 99)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert "metrics" not in result and "device" not in result
    counts = result["rehearsal"]["counts"]
    assert counts["compiles_in_window"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal(capsys, cell):
    result = run_cell(capsys, cell, 5, trace=1)
    assert result["correct"]
    names = result["rehearsal"]["metric_names"]
    assert "round_ms" in names
    # the CPU has no device trace: no device metric is read from it
    assert not {"sort_share", "scatter_share", "merge_gain_ms",
                "merge_gain_roofline"} & set(names)


@pytest.mark.parametrize("cell", CELLS)
def test_state_left_unchanged_fails(capsys, monkeypatch, cell):
    """A merge step that returns its state unchanged."""
    from repro.core import merge
    from repro.core.types import SummaryState

    real = merge.merge_iteration

    def unchanged(src, dst, state, cfg, theta):
        new, stats = real(src, dst, state, cfg, theta)
        return SummaryState(node2super=state.node2super, size=state.size,
                            rng=new.rng, t=new.t), stats

    monkeypatch.setattr(merge, "merge_iteration", unchanged)
    result = run_cell(capsys, cell, 3)
    assert not result["correct"]
    assert result["checks"]["supernode_share"]["value"] == 1.0
    jax.clear_caches()


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_fails(capsys, monkeypatch, cell):
    """A superedge weight altered where the finalize produces it."""
    from repro.core import engine
    from repro.core.types import PairTable

    real = engine.LocalBackend.sparsify_finalize

    def altered(self, state, k_bits, salt):
        out = real(self, state, k_bits, salt)
        pt = out["pair_table"]
        first = int(np.argmax(np.asarray(out["keep"])))
        out["pair_table"] = PairTable(lo=pt.lo, hi=pt.hi,
                                      cnt=pt.cnt.at[first].add(1.0),
                                      valid=pt.valid)
        return out

    monkeypatch.setattr(engine.LocalBackend, "sparsify_finalize", altered)
    result = run_cell(capsys, cell, 4)
    assert not result["correct"] and result["failed"] >= 1
    assert result["checks"]["superedges_wrong"]["value"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_altered_metric_fails(capsys, monkeypatch, cell):
    """The reported RE1 altered by a part in a thousand, about what
    computing it in bfloat16 does."""
    import repro.core

    real = repro.core.summarize

    def altered(*args, **kwargs):
        res = real(*args, **kwargs)
        res.re1 *= 1.001
        return res

    monkeypatch.setattr(repro.core, "summarize", altered)
    result = run_cell(capsys, cell, 6)
    assert not result["correct"]
    assert result["checks"]["re1_gap"]["value"] > 5e-4


def merge_gain_fails(result) -> bool:
    return any(c["value"] > c["limit"] for k, c in result["checks"].items()
               if k.startswith("merge_gain_"))


@pytest.mark.parametrize("cell", CELLS)
def test_merge_gain_in_bfloat16_fails(capsys, monkeypatch, cell):
    """The program's merge gain computed in bfloat16 (Eq. (17)/(20) with
    every value and sum in bfloat16), in the rounds and in the check alike:
    the summary stays consistent, the merge gain's gaps do not."""
    import jax.numpy as jnp

    from harness import merge_gain_check
    from repro.kernels import ops as kops

    def low(*args, **kwargs):
        rel, red, _ = merge_gain_check.reference(*args, xp=jnp,
                                                 dtype=jnp.bfloat16)
        return rel.astype(jnp.float32), red.astype(jnp.float32)

    monkeypatch.setattr(kops, "merge_gain", low)
    result = run_cell(capsys, cell, 8)
    assert not result["correct"]
    assert merge_gain_fails(result)


@pytest.mark.parametrize("cell", CELLS)
def test_altered_merge_gain_fails(capsys, monkeypatch, cell):
    """One pair's Reduction altered where the kernel produces it."""
    from repro.kernels import ops as kops

    real = kops.merge_gain

    def altered(*args, **kwargs):
        rel, red = real(*args, **kwargs)
        return rel, red + 0.01 * args[3][:, :, None]  # 1% of a member's t

    monkeypatch.setattr(kops, "merge_gain", altered)
    result = run_cell(capsys, cell, 9)
    assert not result["correct"]
    assert merge_gain_fails(result)


def test_no_chip_no_result(capsys):
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == "" and "no TPU" in err
