"""Subprocess body for distributed tests (needs its own jax init with fake
devices — run via tests/test_distributed.py, never imported by pytest).

Checks, on an 8-device host mesh:
  1. metric parity: with merges disabled (θ=∞), the distributed step's
     size_bits / re1 equal the single-device closed-form evaluation exactly;
  2. a real distributed run merges nodes, respects monotone size shrink,
     and reports zero bucket overflow;
  3. replicated state stays bit-identical across devices;
  4. sparsify parity: the edge-sharded further-sparsification phase
     (psum'd histogram order statistic) produces a drop mask bit-identical
     to single-host further_sparsify and matching post-drop Size(Ḡ)/RE —
     including the ξ == 0 (budget already met) and ξ ≥ |P| (drop
     everything) degenerate branches;
  5. engine parity: SummaryEngine over the unified DistributedBackend
     (while_loop-chunked driver inside the shard_map body, then the
     sparsify finalize) is bit-identical to an explicit host loop that
     dispatches the same ``chunk`` program one round at a time — for
     both driver_chunk=8 and the history-equivalent driver_chunk=1.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json

import jax
import jax.numpy as jnp
import numpy as np

import dataclasses

from repro.core import costs, sparsify
from repro.core.distributed import (
    make_distributed_backend,
    make_distributed_sparsify,
    make_distributed_step,
    make_distributed_step_compact,
    pad_and_shard_edges,
)
from repro.core.engine import SummaryEngine
from repro.core.types import SummaryConfig, init_state, make_graph
from repro.graphs import generate
from repro.launch.mesh import make_host_mesh


def check_step(step, graph, v, e, cfg, mesh, src_p, dst_p, label):
    """Shared assertions: metric parity with merges disabled + progress."""
    state = init_state(v, 0)
    with mesh:
        _, stats0 = step(src_p, dst_p, state, jnp.float32(1e9), jnp.uint32(1))
    assert int(stats0["overflow"]) == 0, (label, "bucket overflow")
    pt = costs.build_pair_table(graph.src, graph.dst, state)
    m = costs.summary_metrics(pt, state, v, e, cbar_mode=cfg.cbar_mode,
                              re_guard=cfg.re_guard)
    np.testing.assert_allclose(float(stats0["size_bits"]),
                               float(m["size_bits"]), rtol=1e-5,
                               err_msg=label)
    np.testing.assert_allclose(float(stats0["re1"]), float(m["re1"]),
                               rtol=1e-5, atol=1e-9, err_msg=label)
    assert int(stats0["nmerges"]) == 0, label
    assert int(stats0["overflow"]) == 0, label

    state = init_state(v, 0)
    sizes = []
    with mesh:
        for t in range(1, 6):
            theta = 1.0 / (1.0 + t)
            state, stats = step(src_p, dst_p, state, jnp.float32(theta),
                                jnp.uint32(t))
            sizes.append(float(stats["size_bits"]))
            assert int(stats["overflow"]) == 0, label
    merged = v - int(jnp.sum(state.size > 0))
    assert merged > 0, f"{label}: never merged"
    assert sizes == sorted(sizes, reverse=True), label
    n2s = np.asarray(state.node2super)
    assert (np.asarray(state.size)[n2s] > 0).all(), label
    return merged, sizes[-1]


def check_sparsify(graph, v, e, cfg, mesh, src_p, dst_p, state, k_bits,
                   label):
    """Edge-sharded sparsify ≡ single-host further_sparsify at ``k_bits``."""
    sp = make_distributed_sparsify(mesh, cfg, v, e, capacity_factor=64.0)
    with mesh:
        stats, pairs = sp(src_p, dst_p, state, jnp.float32(k_bits),
                          jnp.uint32(7))
    assert int(stats["overflow"]) == 0, (label, "sparsify bucket overflow")

    pt = costs.build_pair_table(graph.src, graph.dst, state)
    drop_s, after_s = sparsify.further_sparsify(
        pt, state, v, e, k_bits, cbar_mode=cfg.cbar_mode,
        re_guard=cfg.re_guard, error_p=cfg.error_p)

    # --- drop mask: bit-identical, compared as {(lo, hi) → dropped} ------
    want = {}
    valid = np.asarray(pt.valid) & np.asarray(after_s["keep"] | drop_s)
    for lo, hi, d in zip(np.asarray(pt.lo)[valid], np.asarray(pt.hi)[valid],
                         np.asarray(drop_s)[valid]):
        want[(int(lo), int(hi))] = bool(d)
    got = {}
    mine = np.asarray(pairs["mine"]) & (np.asarray(pairs["keep"])
                                        | np.asarray(pairs["drop"]))
    for lo, hi, d in zip(np.asarray(pairs["lo"])[mine],
                         np.asarray(pairs["hi"])[mine],
                         np.asarray(pairs["drop"])[mine]):
        key = (int(lo), int(hi))
        assert key not in got, (label, "pair owned twice", key)
        got[key] = bool(d)
    assert got == want, (
        f"{label}: drop mask mismatch "
        f"({len(got)} vs {len(want)} pairs, "
        f"{sum(got.get(k) != want.get(k) for k in want)} differ)")

    # --- post-drop metrics: Size(Ḡ) bit-identical, RE to float tolerance -
    assert float(stats["size_bits"]) == float(after_s["size_bits"]), label
    np.testing.assert_allclose(float(stats["re1"]), float(after_s["re1"]),
                               rtol=1e-6, atol=1e-12, err_msg=label)
    np.testing.assert_allclose(float(stats["re2"]), float(after_s["re2"]),
                               rtol=1e-6, atol=1e-12, err_msg=label)
    np.testing.assert_allclose(float(stats["num_superedges"]),
                               float(after_s["num_superedges"]),
                               err_msg=label)
    return int(stats["dropped"])


def check_engine(backend, cfg, mesh, src_p, dst_p, label):
    """SummaryEngine over the backend ≡ an explicit per-round host loop.

    The engine runs the while_loop-chunked ``backend.chunk`` program; the
    reference dispatches that same program one round at a time (``limit``
    1) with host-python θ and the historical stopping rule — every metric,
    the sparsify payload, and the final partition must be bit-identical.
    (``backend.step``, the straight-line one-round program, is compiled
    apart and may order its float32 sums differently; ``check_step``
    holds it to the single-device metrics instead.)
    """
    import copy

    backend = copy.copy(backend)
    backend.cfg = cfg
    k_bits = cfg.target_bits(backend.input_size_bits())
    state = backend.init()
    stats = {}
    t = 0
    with mesh:
        for t in range(1, cfg.T + 1):
            theta = 1.0 / (1.0 + t) if t < cfg.T else 0.0
            thetas = np.zeros((max(1, cfg.driver_chunk),), np.float32)
            thetas[0] = theta
            state, buf, rounds = backend.chunk(
                src_p, dst_p, state, jnp.asarray(thetas), jnp.uint32(t),
                jnp.float32(k_bits), jnp.int32(1))
            assert int(rounds) == 1, (label, t, int(rounds))
            stats = {k: np.asarray(v)[0] for k, v in buf.items()}
            if float(stats["size_bits"]) <= k_bits:
                break
        ref_sp, _ = backend.sparsify(src_p, dst_p, state,
                                     jnp.float32(k_bits), jnp.uint32(t + 1))

    run = SummaryEngine(backend.bind(src_p, dst_p)).run()
    assert run.iterations_run == t, (label, run.iterations_run, t)
    for k in stats:
        assert float(run.last_stats[k]) == float(stats[k]), (
            label, k, float(run.last_stats[k]), float(stats[k]))
    for k in ref_sp:
        assert float(run.finalize["stats"][k]) == float(ref_sp[k]), (
            label, k, float(run.finalize["stats"][k]), float(ref_sp[k]))
    np.testing.assert_array_equal(np.asarray(run.state.node2super),
                                  np.asarray(state.node2super),
                                  err_msg=label)
    np.testing.assert_array_equal(np.asarray(run.state.size),
                                  np.asarray(state.size), err_msg=label)
    return run


def check_engine_resume(backend, cfg, src_p, dst_p, golden_run):
    """Checkpoint at every chunk boundary, truncate to the first committed
    step, resume — the distributed engine must land bit-identically on the
    golden (uninterrupted) run's metrics, sparsify payload, and partition,
    with the restored state resharded through ``state_sharding()``."""
    import shutil
    import tempfile

    from repro.core.engine import EngineCheckpointer
    from repro.runtime import CheckpointManager

    import copy

    # driver_chunk=2 → several mid-run chunk boundaries to save at; the
    # golden ran chunk=8 (chunking bit-identity is proven above) and
    # driver_chunk is fingerprint-exempt, so the cross-chunk resume is
    # itself part of the contract under test
    bound = copy.copy(backend)
    bound.cfg = dataclasses.replace(cfg, driver_chunk=2)
    bound = bound.bind(src_p, dst_p)
    d = tempfile.mkdtemp(prefix="dist_resume_")
    try:
        ck = EngineCheckpointer(manager=CheckpointManager(d, keep=50),
                                every=1)
        full = SummaryEngine(bound).run(checkpointer=ck)
        assert full.checkpoint_saves >= 1, "no distributed saves happened"
        steps = ck.manager.all_steps()
        for s in steps[1:]:
            shutil.rmtree(os.path.join(d, f"step_{s:010d}"))

        ck2 = EngineCheckpointer(manager=CheckpointManager(d, keep=50),
                                 every=1)
        run = SummaryEngine(bound).run(checkpointer=ck2, resume=True)
        assert run.resumed_from == steps[0], (run.resumed_from, steps)
        assert run.iterations_run == golden_run.iterations_run
        for k in golden_run.last_stats:
            assert float(run.last_stats[k]) == \
                float(golden_run.last_stats[k]), ("resume", k)
        for k in golden_run.finalize["stats"]:
            assert float(run.finalize["stats"][k]) == \
                float(golden_run.finalize["stats"][k]), ("resume final", k)
        np.testing.assert_array_equal(
            np.asarray(run.state.node2super),
            np.asarray(golden_run.state.node2super), err_msg="resume")
        np.testing.assert_array_equal(
            np.asarray(run.state.size),
            np.asarray(golden_run.state.size), err_msg="resume")
        return steps[0]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def main():
    assert jax.device_count() == 8
    src, dst, v = generate("ego-facebook", seed=0, scale=0.05)
    graph, _ = make_graph(src, dst, v)
    e = graph.num_edges
    mesh = make_host_mesh((2, 4), ("data", "model"))
    cfg = SummaryConfig(T=5, k_frac=0.3)
    src_p, dst_p = pad_and_shard_edges(np.asarray(graph.src),
                                       np.asarray(graph.dst), mesh)

    step = make_distributed_step(mesh, cfg, v, e)
    merged, final = check_step(step, graph, v, e, cfg, mesh, src_p, dst_p,
                               "hash-owner")

    # group ownership concentrates records (few groups at tiny |V|), so the
    # routing capacity factor is raised here; at web scale the expected
    # per-destination load is E/n_dev² ≪ cap
    step_c = make_distributed_step_compact(mesh, cfg, v, e,
                                           capacity_factor=64.0)
    merged_c, final_c = check_step(step_c, graph, v, e, cfg, mesh, src_p,
                                   dst_p, "compact group-owner")

    # external-groups (regroup_every) path: grouping fn + step must agree
    # with the fused step's metrics when merges are disabled
    from repro.core.distributed import make_grouping_fn

    step_x = make_distributed_step_compact(mesh, cfg, v, e,
                                           capacity_factor=64.0,
                                           lean_sort=True,
                                           external_groups=True)
    gfn = make_grouping_fn(mesh, cfg, v, lean_sort=True)
    state = init_state(v, 0)
    with mesh:
        groups = gfn(src_p, dst_p, state)
        _, stats_x = step_x(src_p, dst_p, state, jnp.float32(1e9),
                            jnp.uint32(1), groups)
    pt = costs.build_pair_table(graph.src, graph.dst, state)
    m = costs.summary_metrics(pt, state, v, e, cbar_mode=cfg.cbar_mode,
                              re_guard=cfg.re_guard)
    np.testing.assert_allclose(float(stats_x["size_bits"]),
                               float(m["size_bits"]), rtol=1e-5,
                               err_msg="external-groups")
    # and a real merge round through the external path
    with mesh:
        state2, stats2 = step_x(src_p, dst_p, state, jnp.float32(0.2),
                                jnp.uint32(1), groups)
    assert int(stats2["nmerges"]) > 0, "external-groups path never merged"

    # ---- distributed further-sparsification parity ----------------------
    # re-run 5 merge rounds to get a realistic post-merge partition
    state = init_state(v, 0)
    with mesh:
        for t in range(1, 6):
            state, stats = step(src_p, dst_p, state,
                                jnp.float32(1.0 / (1.0 + t)), jnp.uint32(t))
    # merge-round stats describe the pre-merge partition; read the current
    # size off the sparsify step itself (ξ=0 probe) before picking budgets
    probe = make_distributed_sparsify(mesh, cfg, v, e, capacity_factor=64.0)
    with mesh:
        pstats, _ = probe(src_p, dst_p, state, jnp.float32(1e12),
                          jnp.uint32(7))
    size_now = float(pstats["size_bits_before"])
    dropped = check_sparsify(graph, v, e, cfg, mesh, src_p, dst_p, state,
                             0.9 * size_now, "sparsify k=0.9·size")
    assert dropped > 0, "sparsify: ξ>0 case never dropped"
    none = check_sparsify(graph, v, e, cfg, mesh, src_p, dst_p, state,
                          2.0 * size_now, "sparsify ξ=0")
    assert none == 0, "sparsify: ξ=0 case dropped superedges"
    check_sparsify(graph, v, e, cfg, mesh, src_p, dst_p, state, 1.0,
                   "sparsify drop-everything")
    cfg2 = SummaryConfig(T=5, k_frac=0.3, error_p=2)
    check_sparsify(graph, v, e, cfg2, mesh, src_p, dst_p, state,
                   0.9 * size_now, "sparsify error_p=2")

    # ---- engine over the unified backend --------------------------------
    # One backend object, cfg swapped host-side: k_bits/ensure_budget are
    # operands / host logic, so the degenerate-budget cases reuse the
    # compiled programs; only driver_chunk=1 retraces (R=1 buffers).
    backend = make_distributed_backend(mesh, cfg, v, e, grouping="compact",
                                       capacity_factor=64.0, lean_sort=True)
    run8 = check_engine(backend, cfg, mesh, src_p, dst_p, "engine chunk=8")
    run1 = check_engine(backend, dataclasses.replace(cfg, driver_chunk=1),
                        mesh, src_p, dst_p, "engine chunk=1")
    hist_keys = ("size_bits", "re1", "nmerges", "num_supernodes")
    assert [{k: r[k] for k in hist_keys} for r in run8.history] == \
           [{k: r[k] for k in hist_keys} for r in run1.history], \
        "chunked driver history differs from sync-every-round driver"
    # ξ=0 (budget met at t=1) and drop-everything finalize branches
    check_engine(backend,
                 dataclasses.replace(cfg, k_frac=None, k_bits=1e12),
                 mesh, src_p, dst_p, "engine xi=0")
    check_engine(backend,
                 dataclasses.replace(cfg, k_frac=None, k_bits=1.0,
                                     ensure_budget=False),
                 mesh, src_p, dst_p, "engine drop-all")

    # ---- checkpoint/resume parity on the 8-device mesh ------------------
    resumed_step = check_engine_resume(backend, cfg, src_p, dst_p, run8)

    print(json.dumps({"ok": True, "merged": merged, "merged_compact": merged_c,
                      "final_size_bits": final,
                      "final_size_bits_compact": final_c,
                      "sparsify_dropped": dropped,
                      "engine_iterations": run8.iterations_run,
                      "resumed_step": resumed_step}))


if __name__ == "__main__":
    main()
