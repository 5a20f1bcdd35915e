"""Graph-summarization driver (the paper's own workload).

    PYTHONPATH=src python -m repro.launch.summarize --dataset dblp \
        --scale 0.05 --k-frac 0.3 --T 20

    PYTHONPATH=src python -m repro.launch.summarize \
        --edge-list data/dblp.txt.gz --k-frac 0.3 --T 20

Runs SSumM (the vectorized TPU-native implementation) on a registry graph
or a real SNAP edge-list file (``--edge-list``; streamed + CSR-cached via
``repro.graphs.io``, DESIGN.md §10), optionally distributed over every
local device with the edge-sharded shard_map path (``--distributed``),
and prints Eq.(2)/(4) metrics. Registry names resolve real files under
``$SSUMM_DATA_DIR`` first, then the binary cache, then the synthetic
stand-in — the JSON's ``source`` field says which one ran.

Distributed runs with a CSR cache behind them feed the mmap'd edge
columns straight onto the mesh (``repro.graphs.feed``, DESIGN.md §11):
host staging is one shard, never a full-|E| array, and the JSON reports
the feed accounting (``feed_*``) plus ``peak_rss_mb``; ``--rss-budget-mb``
turns the RSS number into a hard exit-status gate (the CI ``ingest`` job
runs the 1.1M-edge fixture under it).

The mesh may span OS processes (DESIGN.md §15): launch the same command
on every host with ``--coordinator host:port --num-processes N
--process-id i`` (or the ``SSUMM_*`` env equivalents) plus
``--distributed``; each process then stages only its addressable shards
from the shared CSR cache and the summary is bit-identical to the
single-process run on the same global device count
(``tests/multihost_check.py``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import numpy as np

from repro.core import SummaryConfig, summarize
from repro.core.distributed import make_distributed_backend
from repro.core.engine import EngineCheckpointer, SummaryEngine
from repro.core.types import make_graph
from repro.graphs import DATASETS, load_graph
from repro.graphs.feed import (
    EdgeShards,
    shard_edges,
    shard_edges_from_cache,
    shard_edges_from_cache_multihost,
)
from repro.launch.device import device_info, enable_compile_cache
from repro.launch.mesh import bootstrap_distributed
from repro.runtime import (
    RESUMABLE_EXIT,
    CheckpointManager,
    Preempted,
    PreemptionGuard,
    StragglerMonitor,
    make_mesh_from_plan,
    plan_mesh,
)


def build_distributed_pipeline(mesh, cfg: SummaryConfig, num_nodes: int,
                               num_edges: int):
    """The jitted distributed backend for one problem size (DESIGN.md §12).

    Each call builds *fresh* jit closures — callers that run the pipeline
    repeatedly at the same shapes (benchmarks timing warm runs) must build
    once and pass the backend to :func:`run_distributed`, otherwise every
    run retraces and recompiles.
    """
    return make_distributed_backend(mesh, cfg, num_nodes, num_edges,
                                    grouping="compact",
                                    capacity_factor=32.0,
                                    lean_sort=True)


def run_distributed(src, dst, v, cfg: SummaryConfig, mesh, pipeline=None,
                    shards: EdgeShards | None = None, *,
                    checkpointer=None, monitor=None, resume: bool = False):
    """Merge rounds + final sparsification, all edge-sharded over ``mesh``.

    Eq.(2)/(4) metrics come out of the psum'd reductions of the sparsify
    step — at no point is the edge list (or the pair table) gathered to a
    single host. Returns ``(state, stats, size_g)`` with ``stats`` holding
    the post-sparsification metrics plus ``sparsify_wall_s``.

    ``shards`` (an :class:`repro.graphs.feed.EdgeShards`) supplies the
    already-sharded edge columns — the out-of-core path
    (``shard_edges_from_cache``) or a benchmark reusing one feed across
    rounds. ``src``/``dst`` are then ignored (pass ``None``). Without it,
    the edge list is canonicalized and fed through the in-memory fallback;
    both paths produce bit-identical metrics (``tests/feed_check.py``).

    The loop itself is :class:`repro.core.engine.SummaryEngine` over the
    distributed backend (DESIGN.md §12): ``cfg.driver_chunk`` merge rounds
    run per dispatch inside the shard_map body, and the Sect. 3.2.4
    drop-to-k tail (distributed ξ-th order statistic, DESIGN.md §7) is the
    backend's finalize.

    ``checkpointer``/``monitor``/``resume`` pass through to the engine
    (DESIGN.md §13); the fault-tolerance bookkeeping rides along inside the
    stats dict (``chunk_wall_s``, ``straggler_events``, ``resumed_from``,
    ``checkpoint_*``).
    """
    if shards is None:
        graph, _ = make_graph(src, dst, v)
        shards = shard_edges(np.asarray(graph.src), np.asarray(graph.dst),
                             mesh)
    elif shards.num_nodes is not None and shards.num_nodes != v:
        # a stale v with cache-fed shards would let edge ids index out of
        # the [v]-sized partition vectors, which jit clamps silently —
        # plausible-but-wrong metrics instead of an error
        raise ValueError(
            f"shards came from a cache with |V|={shards.num_nodes} but "
            f"run_distributed was called with v={v}")
    e = shards.num_edges
    if pipeline is None:
        pipeline = build_distributed_pipeline(mesh, cfg, v, e)
    backend = pipeline.bind(shards.src, shards.dst)
    run = SummaryEngine(backend).run(collect_history=False,
                                     checkpointer=checkpointer,
                                     monitor=monitor, resume=resume)
    out = {k: float(x) for k, x in (run.last_stats or {}).items()}
    sp_stats = {k: float(x) for k, x in run.finalize["stats"].items()}
    sp_stats["sparsify_wall_s"] = run.sparsify_wall_s
    out.update(sp_stats)
    out["iterations"] = run.iterations_run
    out["chunk_wall_s"] = run.chunk_wall_s
    out["straggler_events"] = [dataclasses.asdict(ev)
                               for ev in run.straggler_events]
    out["resumed_from"] = run.resumed_from
    out["checkpoint_saves"] = run.checkpoint_saves
    out["checkpoint_snapshot_wall_s"] = run.checkpoint_snapshot_wall_s
    return run.state, out, run.input_size_bits


def peak_rss_mb() -> float | None:
    """Process high-water RSS in MB (``None`` where unsupported)."""
    try:
        import resource
        import sys

        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # ru_maxrss is KB on linux, bytes on darwin
        return rss / (1 << 20) if sys.platform == "darwin" else rss / 1024.0
    except (ImportError, ValueError):
        return None


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dataset", default="dblp", choices=sorted(DATASETS))
    ap.add_argument("--edge-list", default=None, metavar="PATH",
                    help="SNAP edge-list file (.txt/.csv, optional .gz); "
                         "overrides --dataset/--scale")
    ap.add_argument("--chunk-edges", type=int, default=None,
                    help="ingest chunk size (rows); bounds parser memory")
    ap.add_argument("--reingest", action="store_true",
                    help="force a re-parse even when the CSR cache is fresh")
    ap.add_argument("--scale", type=float, default=0.05,
                    help="subsample factor for the synthetic registry |V|,|E|")
    ap.add_argument("--k-frac", type=float, default=0.3)
    ap.add_argument("--T", type=int, default=20)
    ap.add_argument("--group-size", type=int, default=32)
    ap.add_argument("--distributed", action="store_true",
                    help="edge-sharded shard_map over all local devices")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="jax.distributed coordinator address for a "
                         "process-spanning mesh (DESIGN.md §15); every "
                         "process passes the same value")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="total processes in the mesh (default: "
                         "$SSUMM_NUM_PROCESSES, else single-process)")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this process's rank in [0, --num-processes)")
    ap.add_argument("--rss-budget-mb", type=float, default=None,
                    help="fail (exit 1) if the process peak RSS exceeds "
                         "this many MB — the CI out-of-core gate")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="save resumable Alg. 1 state here at chunk "
                         "boundaries (async, atomic, keep-N); SIGTERM/"
                         f"SIGINT then save-and-exit {RESUMABLE_EXIT}")
    ap.add_argument("--checkpoint-every", type=int, default=1,
                    help="save cadence in completed merge rounds, aligned "
                         "up to chunk boundaries (<=0: only the final and "
                         "preemption saves)")
    ap.add_argument("--checkpoint-keep", type=int, default=3,
                    help="committed checkpoints retained (keep-N GC)")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest committed checkpoint in "
                         "--checkpoint-dir (bit-identical to an "
                         "uninterrupted run; re-plans the mesh for the "
                         "current device count)")
    ap.add_argument("--driver-chunk", type=int, default=None,
                    help="merge rounds per device dispatch (default: "
                         "SummaryConfig.driver_chunk)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    enable_compile_cache()

    # multi-host bootstrap FIRST — jax.distributed.initialize must run
    # before anything queries device state (single-process: no-op)
    dist = bootstrap_distributed(args.coordinator, args.num_processes,
                                 args.process_id)
    if dist.initialized and not args.distributed:
        ap.error("--coordinator/--num-processes only make sense with "
                 "--distributed")

    t_load = time.time()
    g = load_graph(args.edge_list or args.dataset,
                   chunk_edges=args.chunk_edges, refresh=args.reingest,
                   scale=args.scale, seed=args.seed)
    load_wall_s = time.time() - t_load
    src, dst, v = np.asarray(g.src), np.asarray(g.dst), g.num_nodes
    cfg_kw = {} if args.driver_chunk is None else \
        {"driver_chunk": args.driver_chunk}
    cfg = SummaryConfig(T=args.T, k_frac=args.k_frac,
                        group_size=args.group_size, seed=args.seed, **cfg_kw)

    # fault tolerance (DESIGN.md §13): cooperative preemption + chunk-
    # boundary checkpoints; straggler monitor always on (host-side, free)
    monitor = StragglerMonitor()
    monitor.on_straggler(lambda ev: print(
        f"[straggler] dispatch t0={ev.step}: {ev.step_time:.3f}s "
        f"({ev.ratio:.1f}x the {ev.mean:.3f}s EMA)", file=sys.stderr))
    ckp = None
    if args.checkpoint_dir:
        ckp = EngineCheckpointer(
            manager=CheckpointManager(args.checkpoint_dir,
                                      keep=args.checkpoint_keep),
            every=args.checkpoint_every,
            guard=PreemptionGuard(),
            graph_extra={"dataset": args.edge_list or args.dataset},
        )
    ingest = {
        "source": g.source,
        "load_wall_s": load_wall_s,
        "ingest_bytes_parsed": g.stats.bytes_parsed,
        "ingest_chunks": g.stats.chunks,
        "ingest_duplicates_dropped": g.stats.duplicates_dropped,
        "ingest_self_loops_dropped": g.stats.self_loops_dropped,
    }
    t0 = time.time()
    try:
        if args.distributed:
            # elastic re-mesh: the plan always reflects the *current*
            # device count — a resume after device loss lands on the
            # survivor mesh, the replicated state is reshard-on-load, and
            # the edge shards are re-fed below from the mmap cache
            # (DESIGN.md §13); no resharding pass anywhere
            plan = plan_mesh(jax.device_count(), global_batch=1,
                             want_model=1)
            mesh = make_mesh_from_plan(plan)
            # out-of-core feed: a graph backed by a CSR cache goes straight
            # from the mmap'd columns to per-device shards (DESIGN.md §11);
            # only synthetic stand-ins take the in-memory fallback
            t_feed = time.time()
            if dist.process_count > 1:
                # process-spanning mesh: every process slices only its own
                # addressable shards out of the shared cache (DESIGN.md
                # §15) — the single-process feeds refuse this mesh
                if g.cache_dir is None:
                    raise SystemExit(
                        "multi-process summarize needs a CSR-cached graph "
                        "(--edge-list or a cached registry dataset): the "
                        "synthetic in-memory path would materialize the "
                        "full edge list on every host")
                shards = shard_edges_from_cache_multihost(g.cache_dir, mesh)
            elif g.cache_dir is not None:
                shards = shard_edges_from_cache(g.cache_dir, mesh)
            else:
                graph, _ = make_graph(src, dst, v)
                shards = shard_edges(np.asarray(graph.src),
                                     np.asarray(graph.dst), mesh)
            feed_wall_s = time.time() - t_feed
            _state, stats, size_g = run_distributed(
                None, None, v, cfg, mesh, shards=shards,
                checkpointer=ckp, monitor=monitor, resume=args.resume)
            fs = shards.stats
            result = {
                "dataset": args.edge_list or args.dataset, "V": v,
                "E": len(src),
                "mode": f"distributed{dict(mesh.shape)}",
                "size_bits": stats["size_bits"],
                "size_bits_before_sparsify": stats["size_bits_before"],
                "relative_size": stats["size_bits"] / size_g,
                "re1": stats["re1"], "re2": stats["re2"],
                "num_supernodes": stats["num_supernodes"],
                "num_superedges": stats["num_superedges"],
                "superedges_dropped": stats["dropped"],
                "sparsify_wall_s": stats["sparsify_wall_s"],
                "feed_wall_s": feed_wall_s,
                "feed_path": fs.path,
                "feed_shard_rows": fs.shard_rows,
                "feed_shard_bytes": fs.shard_bytes,
                "feed_peak_staging_bytes": fs.peak_staging_bytes,
                "feed_bytes_copied": fs.bytes_copied,
                "feed_local_shards": fs.local_shards,
                "process_count": dist.process_count,
                "process_index": dist.process_index,
                "chunk_wall_s": stats["chunk_wall_s"],
                "straggler_events": stats["straggler_events"],
                "resumed_from": stats["resumed_from"],
                "checkpoint_saves": stats["checkpoint_saves"],
                "checkpoint_snapshot_wall_s":
                    stats["checkpoint_snapshot_wall_s"],
                "wall_s": time.time() - t0,
            }
        else:
            res = summarize(src, dst, v, cfg, checkpointer=ckp,
                            monitor=monitor, resume=args.resume)
            result = {
                "dataset": args.edge_list or args.dataset, "V": v,
                "E": len(src),
                "mode": "local",
                "size_bits": res.size_bits,
                "relative_size": res.size_bits / res.input_size_bits,
                "re1": res.re1, "re2": res.re2,
                "num_supernodes": res.num_supernodes,
                "num_superedges": res.num_superedges,
                "iterations": res.iterations_run,
                "chunk_wall_s": res.chunk_wall_s,
                "straggler_events": [dataclasses.asdict(ev)
                                     for ev in res.straggler_events],
                "resumed_from": res.resumed_from,
                "checkpoint_saves": res.checkpoint_saves,
                "checkpoint_snapshot_wall_s":
                    res.checkpoint_snapshot_wall_s,
                "wall_s": time.time() - t0,
            }
    except Preempted as p:
        # save-and-exit: the committed checkpoint is the resume point;
        # RESUMABLE_EXIT tells the supervisor "rerun me with --resume"
        print(json.dumps(dict(
            ingest, preempted=True, checkpoint_step=p.step,
            checkpoint_dir=args.checkpoint_dir,
            wall_s=time.time() - t0), indent=1))
        raise SystemExit(RESUMABLE_EXIT)
    result.update(ingest)
    result["device"] = device_info()
    if ckp is not None:
        result["checkpoint_dir"] = args.checkpoint_dir
    result["peak_rss_mb"] = peak_rss_mb()
    print(json.dumps(result, indent=1))
    if (args.rss_budget_mb is not None and result["peak_rss_mb"] is not None
            and result["peak_rss_mb"] > args.rss_budget_mb):
        raise SystemExit(
            f"peak RSS {result['peak_rss_mb']:.1f} MB exceeds the "
            f"--rss-budget-mb {args.rss_budget_mb:.1f} MB gate")
    return result


if __name__ == "__main__":
    main()
