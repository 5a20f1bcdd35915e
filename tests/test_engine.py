"""SummaryEngine: chunk-size invariance, an independent check of the final
summary, checkpoint/resume and preemption.

Every per-round history metric and the final ``SummaryResult`` are
bit-identical for any ``driver_chunk`` (``driver_chunk=1`` is the
history-equivalent sync-every-round mode; the distributed analogue lives in
``tests/dist_check.py``), and the final summary agrees with a float64
dense reconstruction of the same partition.
"""

import dataclasses
import pathlib

import numpy as np
import pytest

from repro.core import SummaryConfig, summarize
from repro.core.engine import (
    EngineCheckpointer,
    FingerprintMismatch,
    LocalBackend,
    SummaryEngine,
    theta_schedule_host,
)
from repro.core.evaluate import (
    dense_adjacency,
    re_p_dense,
    reconstruct_dense,
    summary_size_bits_dense,
)
from repro.graphs import generate
from repro.runtime import (
    CheckpointManager,
    Preempted,
    PreemptionGuard,
    StragglerMonitor,
)

#: the chunk-invariance fixture: a 323-node ego-facebook stand-in
FIXTURE = dict(name="ego-facebook", seed=0, scale=0.08)
CONFIG = dict(T=10, k_frac=0.3, seed=1)

HISTORY_KEYS = ("size_bits", "re1", "re2", "nmerges", "num_supernodes",
                "num_superedges", "mdl_cost", "t", "theta")

#: SummaryResult fields that are wall-clock bookkeeping, not results
TIMING_FIELDS = ("chunk_wall_s", "checkpoint_snapshot_wall_s")

#: relative gap allowed between the engine's float32 RE₁, RE₂ and Size(Ḡ)
#: and the float64 dense reference. Each is a float32 sum of a few hundred
#: terms at |V| = 323 (489 superedges over 186 supernodes), each rounded at
#: the float32 unit roundoff 2⁻²⁴ ≈ 6e-8: the gaps read 2.1e-8 (RE₁),
#: 7e-9 (RE₂) and 2.3e-8 (size), and 1e-6 (about 17 roundoffs) leaves
#: ~40× room. One wrong superedge moves Size(Ḡ) by ~22 bits of 13.1k
#: (≈2e-3), so the bound still sees any wrong term.
DENSE_RTOL = 1e-6


def _load():
    return generate(**FIXTURE)


@pytest.fixture(scope="module")
def chunk8_run():
    src, dst, v = _load()
    return summarize(src, dst, v, SummaryConfig(**CONFIG, driver_chunk=8))


def _assert_results_identical(got, want):
    for f in dataclasses.fields(want):
        if f.name in TIMING_FIELDS:
            continue
        a, b = getattr(got, f.name), getattr(want, f.name)
        if isinstance(b, np.ndarray):
            assert a.dtype == b.dtype and np.array_equal(a, b), f.name
        else:
            assert a == b, (f.name, a, b)


def _assert_matches_dense_reference(res, src, dst, v):
    a = dense_adjacency(src, dst, v)
    a_hat = reconstruct_dense(res)
    for p, got in ((1, res.re1), (2, res.re2)):
        want = re_p_dense(a, a_hat, p)
        assert abs(got - want) <= DENSE_RTOL * want, (p, got, want)
    size = summary_size_bits_dense(res)
    assert abs(res.size_bits - size) <= DENSE_RTOL * size, (res.size_bits,
                                                            size)
    # supernode sizes and superedge weights: exact counts from the partition
    n2s = res.node2super
    np.testing.assert_array_equal(res.super_size,
                                  np.bincount(n2s, minlength=v))
    assert res.num_supernodes == len(np.unique(n2s))
    lo = np.minimum(src, dst)
    hi = np.maximum(src, dst)
    keep = lo != hi
    ekey = np.unique(lo[keep].astype(np.int64) * v + hi[keep])
    s_lo = np.minimum(n2s[ekey // v], n2s[ekey % v]).astype(np.int64)
    s_hi = np.maximum(n2s[ekey // v], n2s[ekey % v]).astype(np.int64)
    pair, count = np.unique(s_lo * v + s_hi, return_counts=True)
    got = res.edge_lo.astype(np.int64) * v + res.edge_hi
    assert res.num_superedges == len(got) == len(np.unique(got))
    pos = np.searchsorted(pair, got)
    assert np.all(pair[np.minimum(pos, len(pair) - 1)] == got)
    np.testing.assert_array_equal(res.edge_w, count[pos])


@pytest.mark.parametrize("driver_chunk", [8, 1, 3])
def test_local_engine_matches_golden(driver_chunk, chunk8_run):
    """Any chunk size (8 again, 1, 3) reproduces the ``driver_chunk=8`` run
    bit for bit, and its summary matches the float64 dense reference."""
    src, dst, v = _load()
    res = summarize(src, dst, v,
                    SummaryConfig(**CONFIG, driver_chunk=driver_chunk))

    assert len(res.history) == len(chunk8_run.history) == CONFIG["T"]
    for got, want in zip(res.history, chunk8_run.history):
        assert got.keys() == want.keys()
        for k in want:
            assert got[k] == want[k], (driver_chunk, got["t"], k,
                                       got[k], want[k])
    _assert_results_identical(res, chunk8_run)
    _assert_matches_dense_reference(res, src, dst, v)


def test_theta_schedule_host_matches_paper():
    # Eq. (21): θ(t) = (1+t)⁻¹ before the last round, 0 at t = T
    assert theta_schedule_host(1, 10) == 0.5
    assert theta_schedule_host(9, 10) == 0.1
    assert theta_schedule_host(10, 10) == 0.0


def test_engine_run_payload_consistent():
    """EngineRun bookkeeping: k_bits, last_stats, and history agree."""
    src, dst, v = _load()
    cfg = SummaryConfig(T=4, k_frac=0.3, seed=1)
    backend = LocalBackend(src, dst, v, cfg)
    run = SummaryEngine(backend).run()
    assert run.k_bits == cfg.target_bits(run.input_size_bits)
    assert run.iterations_run == len(run.history)
    assert run.last_stats is not None
    for k in backend.stat_keys:
        assert run.last_stats[k] == run.history[-1][k]
    assert run.sparsify_wall_s >= 0.0
    assert "after" in run.finalize
    # one dispatch per chunk, all timed; no checkpointer/monitor → zeros
    assert len(run.chunk_wall_s) == 1 and run.chunk_wall_s[0] > 0.0
    assert run.straggler_events == []
    assert run.resumed_from is None and run.checkpoint_saves == 0


# ---------------------------------------------------------------------------
# checkpoint / resume (DESIGN.md §13)
# ---------------------------------------------------------------------------


def _ckp(tmp_path, name="ck", **kw):
    return EngineCheckpointer(
        manager=CheckpointManager(str(tmp_path / name), keep=kw.pop("keep", 50)),
        **kw)


def _drop_steps_after(mgr, keep_step):
    import shutil

    for s in mgr.all_steps():
        if s > keep_step:
            shutil.rmtree(pathlib.Path(mgr.dir) / f"step_{s:010d}")


@pytest.mark.parametrize("resume_chunk", [3, 1, 8])
def test_local_resume_bit_identical(tmp_path, resume_chunk):
    """Kill-at-any-chunk-boundary → resume ≡ the uninterrupted run.

    The golden is a plain run; the interrupted run checkpoints every
    chunk, everything after the *first* committed step is deleted
    (equivalent to dying right after that boundary), and the resume —
    even under a different ``driver_chunk`` — must reproduce every final
    metric and the partition bit-for-bit.
    """
    src, dst, v = _load()
    cfg = SummaryConfig(T=10, k_frac=0.2, seed=0, driver_chunk=3)
    golden = summarize(src, dst, v, cfg)

    ck = _ckp(tmp_path, every=1)
    full = summarize(src, dst, v, cfg, checkpointer=ck)
    assert full.checkpoint_saves >= 2
    steps = ck.manager.all_steps()
    assert steps, "no committed checkpoints"
    _drop_steps_after(ck.manager, steps[0])

    cfg_r = dataclasses.replace(cfg, driver_chunk=resume_chunk)
    ck2 = _ckp(tmp_path, every=1)
    res = summarize(src, dst, v, cfg_r, checkpointer=ck2, resume=True)
    assert res.resumed_from == steps[0]
    for k in ("size_bits", "input_size_bits", "re1", "re2", "mdl_cost",
              "num_supernodes", "num_superedges", "iterations_run"):
        assert getattr(res, k) == getattr(golden, k), k
    np.testing.assert_array_equal(res.node2super, golden.node2super)
    np.testing.assert_array_equal(res.super_size, golden.super_size)
    np.testing.assert_array_equal(res.edge_w, golden.edge_w)
    # resumed history continues the golden's round numbering seamlessly
    assert [h["t"] for h in golden.history] == \
        list(range(1, golden.iterations_run + 1))
    got_hist = [{k: h[k] for k in HISTORY_KEYS} for h in res.history]
    want_hist = [{k: h[k] for k in HISTORY_KEYS} for h in golden.history]
    assert got_hist == want_hist


def test_resume_from_final_phase_skips_merging(tmp_path):
    """A crash inside the sparsify tail resumes straight to finalize."""
    src, dst, v = _load()
    cfg = SummaryConfig(T=4, k_frac=0.3, seed=0)
    golden = summarize(src, dst, v, cfg)

    ck = _ckp(tmp_path, every=0)  # only the merge-done (phase=final) save
    full = summarize(src, dst, v, cfg, checkpointer=ck)
    assert full.checkpoint_saves == 1
    ck2 = _ckp(tmp_path, every=0)
    res = summarize(src, dst, v, cfg, checkpointer=ck2, resume=True)
    assert res.resumed_from == golden.iterations_run
    # no merge rounds re-ran: the only dispatches were... none at all
    assert res.chunk_wall_s == []
    assert res.size_bits == golden.size_bits
    np.testing.assert_array_equal(res.node2super, golden.node2super)


def test_resume_fingerprint_gate(tmp_path):
    """A checkpoint is only resumable under the identical config+graph —
    except ``driver_chunk``, whose bit-identity across values is proven."""
    src, dst, v = _load()
    cfg = SummaryConfig(T=4, k_frac=0.3, seed=0)
    ck = _ckp(tmp_path, every=1)
    summarize(src, dst, v, cfg, checkpointer=ck)

    with pytest.raises(FingerprintMismatch, match="config"):
        summarize(src, dst, v, dataclasses.replace(cfg, group_size=16),
                  checkpointer=_ckp(tmp_path, every=1), resume=True)
    with pytest.raises(FingerprintMismatch, match="graph"):
        summarize(src[:-10], dst[:-10], v,
                  cfg, checkpointer=_ckp(tmp_path, every=1), resume=True)
    with pytest.raises(FingerprintMismatch, match="graph"):
        summarize(src, dst, v, cfg, resume=True,
                  checkpointer=_ckp(tmp_path, every=1,
                                    graph_extra={"dataset": "other"}))
    # driver_chunk is exempt: this must NOT raise
    res = summarize(src, dst, v, dataclasses.replace(cfg, driver_chunk=1),
                    checkpointer=_ckp(tmp_path, every=1), resume=True)
    assert res.resumed_from is not None


def test_resume_with_empty_dir_starts_fresh(tmp_path):
    """``--resume`` against a dir with nothing committed is a cold start,
    not an error — the idempotent supervisor retry loop depends on it."""
    src, dst, v = _load()
    cfg = SummaryConfig(T=3, k_frac=0.3, seed=0)
    golden = summarize(src, dst, v, cfg)
    res = summarize(src, dst, v, cfg, checkpointer=_ckp(tmp_path, every=1),
                    resume=True)
    assert res.resumed_from is None
    assert res.size_bits == golden.size_bits


def test_resume_requires_checkpointer():
    src, dst, v = _load()
    backend = LocalBackend(src, dst, v, SummaryConfig(T=2))
    with pytest.raises(ValueError, match="checkpointer"):
        SummaryEngine(backend).run(resume=True)


def test_preemption_saves_and_raises(tmp_path):
    """A pending signal is honored at the next host-sync point: the state
    is saved synchronously and ``Preempted`` carries the committed step."""
    src, dst, v = _load()
    cfg = SummaryConfig(T=10, k_frac=0.2, seed=0, driver_chunk=2)
    guard = PreemptionGuard(signals=())
    guard._requested = True  # signal already pending before the run
    ck = _ckp(tmp_path, every=1, guard=guard)
    with pytest.raises(Preempted) as ei:
        summarize(src, dst, v, cfg, checkpointer=ck)
    assert ei.value.step == 2  # first chunk boundary
    assert ck.manager.latest_step() == 2

    golden = summarize(src, dst, v, cfg)
    res = summarize(src, dst, v, cfg, checkpointer=_ckp(tmp_path, every=1),
                    resume=True)
    assert res.resumed_from == 2
    assert res.size_bits == golden.size_bits
    np.testing.assert_array_equal(res.node2super, golden.node2super)


def test_straggler_monitor_brackets_dispatches():
    src, dst, v = _load()
    cfg = SummaryConfig(T=6, k_frac=0.2, seed=0, driver_chunk=2)
    mon = StragglerMonitor(warmup_steps=1000)  # never flags
    res = summarize(src, dst, v, cfg, monitor=mon)
    assert mon.count == len(res.chunk_wall_s) > 0
    assert res.straggler_events == []
