#!/usr/bin/env python3
"""Chip smoke: SSumM's two user paths, end to end, on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip paths only

One chip: generates the ``amazon0601`` stand-in at full scale from
``--seed`` (|V| = 403,394, |E| = 2,419,961 after canonicalization),
summarizes it within k = 0.3·Size(G) bits with the compiled Pallas
merge-gain kernel through ``repro.core.summarize``, and checks the summary
against a numpy recomputation of Eq. (2)/(4). It then runs one round's
group tables through the ``"pallas"`` and ``"ref"`` kernels, and answers a
64-slot batch of each of the seven query kinds through ``QueryServer``,
checking every answer against ``repro.core.queries``.

Four chips: ``run_distributed`` over a 4-device mesh on the same graph
(merge-free step metrics against the single-device closed form, the budget,
a partition replicated on every device, an edge shard resident on each
device, and the numpy recomputation), then the ``PartitionedQueryEngine``
answering the query batches with the same answer digest as the
single-device ``QueryEngine``.

Progress, the device, the kernel backend and all times go to earlier lines;
times are set-up and smoke times, not benchmark metrics. The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or when any check fails, the script exits non-zero and
prints no such line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

DATASET = "amazon0601"
K_FRAC = 0.3
T = 20
SLOTS = 64
KERNEL = "pallas"
METRIC_RTOL = 1e-4   # numpy Eq. (2)/(4) vs the device's float32 values
KERNEL_TOL = 1e-5    # "pallas" vs "ref" merge-gain matrices (rtol = atol)
QUERY_EXACT = 1e-9   # float64 answers vs repro.core.queries ...
QUERY_MAX = 1e-6     # ... and the most this smoke tolerates


class SmokeFailure(RuntimeError):
    pass


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def say(event: str, **fields) -> None:
    print(json.dumps({"event": event, **fields}, default=float), flush=True)


def timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


# ------------------------------------------------------------ summarization


def recompute_summary(res, src, dst, v: int) -> dict:
    """Eq. (4) size and Eq. (2) RE₁ of ``res``, recomputed in float64 numpy
    from the returned partition and superedges (``core/ref_numpy.py``
    semantics, vectorized). Also checks every kept ω against the true
    subedge count of its supernode pair."""
    n2s = np.asarray(res.node2super, np.int64)
    a, b = n2s[src], n2s[dst]
    keys, cnt = np.unique(np.minimum(a, b) * v + np.maximum(a, b),
                          return_counts=True)
    kept = (np.asarray(res.edge_lo, np.int64) * v
            + np.asarray(res.edge_hi, np.int64))
    check(np.unique(kept).size == kept.size, "a superedge is kept twice")
    pos = np.minimum(np.searchsorted(keys, kept), keys.size - 1)
    check(np.array_equal(keys[pos], kept),
          "a kept superedge joins supernodes with no subedge between them")
    check(np.array_equal(np.asarray(res.edge_w, np.int64), cnt[pos]),
          "a superedge weight differs from its true subedge count")

    size = np.asarray(res.super_size, np.float64)
    check(np.array_equal(np.bincount(n2s, minlength=v), size),
          "super_size differs from the member counts of node2super")
    s = max(int(np.count_nonzero(size)), 2)
    p = kept.size
    w_max = max(int(cnt[pos].max()) if p else 0, 2)
    size_bits = p * (2 * math.log2(s) + math.log2(w_max)) + v * math.log2(s)

    plo, phi = keys // v, keys % v
    pi = np.where(plo == phi, size[plo] * (size[plo] - 1) / 2,
                  size[plo] * size[phi])
    keep = np.zeros(keys.size, bool)
    keep[pos] = True
    sigma = cnt / np.maximum(pi, 1.0)
    re1 = 2 * np.sum(np.where(keep, 2 * cnt * (1 - sigma), cnt)) / (
        v * (v - 1.0))
    return {"size_bits": size_bits, "re1": float(re1),
            "num_supernodes": int(np.count_nonzero(size)),
            "num_superedges": p}


def check_summary(res, src, dst, v: int, k_bits: float, label: str,
                  **fields) -> None:
    """Print the summary's metrics beside their numpy recomputation, then
    hold it to the budget and to them."""
    want = recompute_summary(res, src, dst, v)
    say(label, V=v, E=int(src.size), iterations=res.iterations_run,
        k_bits=k_bits, size_bits=res.size_bits,
        numpy_size_bits=want["size_bits"],
        relative_size=res.size_bits / res.input_size_bits, re1=res.re1,
        numpy_re1=want["re1"], num_supernodes=res.num_supernodes,
        num_superedges=res.num_superedges, **fields)
    check(res.size_bits <= k_bits,
          f"{label}: size_bits {res.size_bits} exceeds k = {k_bits}")
    check(want["size_bits"] <= k_bits,
          f"{label}: recomputed size {want['size_bits']} exceeds k")
    for key in ("num_supernodes", "num_superedges"):
        check(getattr(res, key) == want[key],
              f"{label}: {key} {getattr(res, key)} != numpy {want[key]}")
    for key in ("size_bits", "re1"):
        got = getattr(res, key)
        check(math.isclose(got, want[key], rel_tol=METRIC_RTOL),
              f"{label}: {key} {got} != numpy {want[key]}")


def summarize_phase(src, dst, v: int, seed: int):
    from repro.core import SummaryConfig, summarize
    from repro.core.queries import build_block_summary

    cfg = SummaryConfig(T=T, k_frac=K_FRAC, kernel_backend=KERNEL, seed=seed)
    res, cold_s = timed(summarize, src, dst, v, cfg, collect_history=False)
    again, warm_s = timed(summarize, src, dst, v, cfg, collect_history=False)
    check(np.array_equal(res.node2super, again.node2super)
          and res.size_bits == again.size_bits,
          "two summarize runs of one graph and seed differ")
    bs = build_block_summary(res)  # the query engine's CSR, memoized on res
    check_summary(res, src, dst, v, cfg.target_bits(res.input_size_bits),
                  "summarize", backend=KERNEL, first_run_s=cold_s,
                  warm_run_s=warm_s, compile_s=cold_s - warm_s,
                  block_csr={"S": bs.num_blocks, "nnz": bs.nnz,
                             "D": bs.max_row_nnz()})
    return res, cfg


def kernel_phase(src, dst, res, cfg) -> None:
    """One round's group tables (from the final partition) through the
    compiled Pallas kernel and the jnp oracle, on the chip."""
    import jax
    import jax.numpy as jnp

    from repro.core import merge
    from repro.core.types import SummaryState
    from repro.kernels import ops as kops

    v = int(res.node2super.shape[0])
    state = SummaryState(node2super=jnp.asarray(res.node2super),
                         size=jnp.asarray(res.super_size),
                         rng=jax.random.PRNGKey(cfg.seed),
                         t=jnp.int32(res.iterations_run + 1))
    operands = jax.jit(merge.scoring_operands, static_argnames=("cfg",))
    (gt, metrics), tables_s = timed(
        lambda: jax.block_until_ready(operands(
            jnp.asarray(src), jnp.asarray(dst), state, cfg=cfg,
            k_groups=jax.random.PRNGKey(cfg.seed + 1))))
    args = (gt.m, gt.n, gt.s, gt.t, gt.n_u, gt.cidx, gt.w, metrics["cbar"],
            jnp.log2(jnp.float32(v)))
    out, times = {}, {}
    for backend in ("pallas", "ref"):
        def run(backend=backend):
            return jax.block_until_ready(kops.merge_gain(*args,
                                                         backend=backend))
        _, first_s = timed(run)
        (rel, red), warm_s = timed(run)
        out[backend] = (np.asarray(rel), np.asarray(red))
        times[backend] = {"first_s": first_s, "warm_s": warm_s}
    (rel_p, red_p), (rel_r, red_r) = out["pallas"], out["ref"]
    fin_p, fin_r = np.isfinite(rel_p), np.isfinite(rel_r)
    both = fin_p & fin_r
    rel_diff = np.abs(rel_p[both] - rel_r[both])
    # red = denom - merged sums pair costs: held to 1e-6 of t_i + t_j
    red_tol = 1e-6  # about eight float32 ulps of those costs
    t = np.abs(np.asarray(gt.t))
    cost_scale = np.maximum(t[:, :, None] + t[:, None, :], 1.0)
    red_diff = np.abs(red_p - red_r)
    say("kernel", shape=list(gt.m.shape), valid_pairs=int(fin_p.sum()),
        rel_max_abs_diff=float(rel_diff.max(initial=0.0)),
        red_max_abs_diff=float(red_diff.max()),
        red_max_diff_over_cost=float((red_diff / cost_scale).max()),
        red_allclose_1e5=bool(np.allclose(red_p, red_r, rtol=KERNEL_TOL,
                                          atol=KERNEL_TOL)),
        tables_s=tables_s, times=times)
    check(np.array_equal(fin_p, fin_r), "pallas and ref -inf masks differ")
    check(np.array_equal(np.isneginf(rel_p), ~fin_p), "pallas rel has nan")
    check(np.allclose(rel_p[fin_p], rel_r[fin_r], rtol=KERNEL_TOL,
                      atol=KERNEL_TOL), "pallas rel differs from ref")
    check(np.all(red_diff <= red_tol * cost_scale),
          "pallas red differs from ref by more than 1e-6 of the pair cost")


# ------------------------------------------------------------------ queries


def serve_batches(engine, v: int, seed: int) -> dict:
    """One ``SLOTS``-wide batch per query kind through ``QueryServer``;
    returns ``{kind name: (requests, seconds)}``."""
    from repro.core.queries_jax import KIND_NAMES
    from repro.launch.query_serve import QueryServer, random_workload

    rng = np.random.default_rng(seed)
    served = {}
    for name, kind in KIND_NAMES.items():
        server = QueryServer(engine, slots=SLOTS)
        for req in random_workload(rng, v, SLOTS, [kind]):
            server.submit(req)
        t0 = time.perf_counter()
        check(server.step() and not server.queue,
              f"{name}: the batch did not fit one step")
        served[name] = (server.done, time.perf_counter() - t0)
    return served


def reference_answer(res, req, pagerank) -> float:
    from repro.core import queries as Q
    from repro.core.queries_jax import (KIND_ADJACENCY, KIND_CONDUCTANCE,
                                        KIND_CUT, KIND_DEGREE, KIND_KHOP,
                                        KIND_PAGERANK, KIND_TRIANGLE)

    if req.kind == KIND_DEGREE:
        return Q.expected_degree(res, req.u)
    if req.kind == KIND_ADJACENCY:
        return Q.adjacency_weight(res, req.u, req.v)
    if req.kind == KIND_PAGERANK:
        return float(pagerank[req.u])
    if req.kind == KIND_TRIANGLE:
        return Q.triangle_density(res)
    if req.kind == KIND_KHOP:
        return Q.k_hop_size(res, req.u, req.v)
    if req.kind == KIND_CUT:
        return Q.cut_weight(res, req.a, req.b)
    if req.kind == KIND_CONDUCTANCE:
        return Q.conductance(res, req.a)
    raise ValueError(req.kind)


def check_answers(res, served: dict, label: str) -> float:
    """Every answer against ``repro.core.queries``; returns the largest
    relative error (absolute where the reference is 0)."""
    from repro.core import queries as Q

    pagerank = Q.pagerank_summary(res)
    worst, per_kind = 0.0, {}
    for name, (done, secs) in served.items():
        errs = []
        for req in done:
            want = reference_answer(res, req, pagerank)
            check(np.isfinite(req.answer), f"{label}: {name} is not finite")
            errs.append(abs(req.answer - want) / (abs(want) or 1.0))
        per_kind[name] = {"max_rel_err": max(errs), "step_s": secs}
        worst = max(worst, max(errs))
    say(f"{label}_queries", slots=SLOTS, max_rel_err=worst,
        exact=worst <= QUERY_EXACT, per_kind=per_kind)
    check(worst <= QUERY_MAX,
          f"{label}: largest query error {worst} exceeds {QUERY_MAX}")
    return worst


def query_phase(res, v: int, seed: int) -> None:
    from repro.core.queries_jax import QueryEngine

    engine, build_s = timed(QueryEngine, res)
    served = serve_batches(engine, v, seed)
    say("query_engine", build_s=build_s)
    check_answers(res, served, "local")


# -------------------------------------------------------------- four chips


def four_chip_phase(src, dst, v: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import SummaryConfig
    from repro.core.queries_jax import PartitionedQueryEngine, QueryEngine
    from repro.core.types import SummaryResult
    from repro.graphs.feed import shard_edges
    from repro.launch.query_serve import answers_digest
    from repro.launch.summarize import (build_distributed_pipeline,
                                        run_distributed)
    from repro.runtime import make_mesh_from_plan, plan_mesh

    cfg = SummaryConfig(T=T, k_frac=K_FRAC, kernel_backend=KERNEL, seed=seed)
    mesh = make_mesh_from_plan(plan_mesh(4, global_batch=1, want_model=1))
    e = int(src.size)
    shards, shard_s = timed(shard_edges, src, dst, mesh)
    for col in (shards.src, shards.dst):
        homes = [sh.device for sh in col.addressable_shards]
        check(len(set(homes)) == 4 and all(
            sh.data.devices() == {sh.device}
            for sh in col.addressable_shards),
            "an edge shard is not resident on its own device")
    say("edge_shards", devices=[str(d) for d in homes], shard_s=shard_s)
    pipeline = build_distributed_pipeline(mesh, cfg, v, e)
    backend = pipeline.bind(shards.src, shards.dst)

    # merges disabled (θ = ∞): the round's metrics are the closed form's,
    # here Eq. (2)/(4) of the all-singleton partition recomputed in numpy
    state0 = backend.init()
    thetas = jnp.full((cfg.driver_chunk,), 1e9, jnp.float32)
    (_, buf, _), first_s = timed(
        lambda: jax.block_until_ready(backend.run_chunk(state0, thetas, 1,
                                                        0.0, 1)))
    closed = recompute_summary(SummaryResult(
        node2super=np.arange(v, dtype=np.int32), super_size=np.ones(v),
        edge_lo=src, edge_hi=dst, edge_w=np.ones(e, np.int64),
        num_supernodes=v, num_superedges=e, size_bits=0.0,
        input_size_bits=0.0, re1=0.0, re2=0.0, mdl_cost=0.0,
        iterations_run=0), src, dst, v)
    say("theta_inf_round", size_bits=float(buf["size_bits"][0]),
        closed_size_bits=closed["size_bits"], re1=float(buf["re1"][0]),
        closed_re1=closed["re1"], nmerges=float(buf["nmerges"][0]),
        first_s=first_s)
    check(float(buf["nmerges"][0]) == 0, "θ = ∞ still merged")
    for key in ("size_bits", "re1"):
        check(math.isclose(float(buf[key][0]), closed[key], rel_tol=1e-5,
                           abs_tol=1e-9),
              f"θ = ∞ {key} {float(buf[key][0])} != {closed[key]}")

    (state, stats, size_g), run_s = timed(
        run_distributed, None, None, v, cfg, mesh, pipeline=pipeline,
        shards=shards)
    k_bits = cfg.target_bits(size_g)
    copies = [np.asarray(sh.data) for sh in state.node2super.addressable_shards]
    check(len(copies) == 4 and all(np.array_equal(copies[0], c)
                                   for c in copies[1:]),
          "node2super differs between devices")

    # the kept superedges, re-derived by the (deterministic) finalize
    pairs = backend.sparsify_finalize(state, k_bits,
                                      stats["iterations"] + 1)["pairs"]
    mask = np.asarray(pairs["keep"]) & np.asarray(pairs["mine"])
    res = SummaryResult(
        node2super=copies[0], super_size=np.asarray(state.size),
        edge_lo=np.asarray(pairs["lo"])[mask],
        edge_hi=np.asarray(pairs["hi"])[mask],
        edge_w=np.asarray(pairs["cnt"])[mask].astype(np.int64),
        num_supernodes=int(stats["num_supernodes"]),
        num_superedges=int(stats["num_superedges"]),
        size_bits=stats["size_bits"], input_size_bits=size_g,
        re1=stats["re1"], re2=stats["re2"], mdl_cost=float("nan"),
        iterations_run=int(stats["iterations"]))
    check_summary(res, src, dst, v, k_bits, "distributed_summarize",
                  run_s=run_s, sparsify_wall_s=stats["sparsify_wall_s"])
    engine, build_s = timed(PartitionedQueryEngine, res, mesh)
    say("partitioned_engine", build_s=build_s, **engine.partition_stats())
    part = serve_batches(engine, v, seed)
    check_answers(res, part, "partitioned")
    local = serve_batches(QueryEngine(res), v, seed)
    digests = {label: answers_digest([r for done, _ in got.values()
                                      for r in done])
               for label, got in (("local", local), ("partitioned", part))}
    gaps = {}  # per kind, the largest relative local-partitioned gap
    for name, (done, _) in local.items():
        ref = {r.rid: r.answer for r in done}
        gaps[name] = max(abs(r.answer - ref[r.rid]) / (abs(ref[r.rid]) or 1.0)
                         for r in part[name][0])
    say("digests", local_s={k: t for k, (_, t) in local.items()},
        max_rel_gap=gaps, **digests)
    check(digests["local"] == digests["partitioned"], "the digests differ")


# --------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.device import device_info, enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    device = device_info()
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {device['platform']})",
              file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{device['count']}", file=sys.stderr)
        return 1
    say("device", **device, compile_cache=cache_dir, jax=jax.__version__)

    from repro.graphs.synthetic import generate

    (src, dst, v), gen_s = timed(generate, DATASET, seed=args.seed, scale=1.0)
    say("graph", dataset=DATASET, V=v, E=int(src.size), generate_s=gen_s)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase(src, dst, v, args.seed)
    else:
        res, cfg = summarize_phase(src, dst, v, args.seed)
        kernel_phase(src, dst, res, cfg)
        query_phase(res, v, args.seed)
    stats = jax.devices()[0].memory_stats() or {}
    say("done", smoke_s=time.perf_counter() - t0,
        peak_hbm_bytes=stats.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": dict(device, count=args.chips)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
