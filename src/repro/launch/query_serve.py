"""Batched summary-query serving driver (DESIGN.md §14).

    PYTHONPATH=src python -m repro.launch.query_serve --dataset dblp \
        --scale 0.05 --k-frac 0.3 --T 10 --requests 512 --batch 64

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.query_serve --edge-list g.txt.gz \
        --distributed --requests 256 --batch 64

Summarizes the graph (or loads it through the same registry/CSR-cache
resolution as ``launch.summarize``), builds the device-resident
:class:`repro.core.queries_jax.QueryEngine` (``--distributed``: the
owner-routed :class:`RoutedQueryEngine` over every local device, or with
``--tier partitioned`` the memory-partitioned
:class:`PartitionedQueryEngine`), and serves a mixed analytics workload —
expected degree, adjacency weight, PageRank, triangle density, k-hop
neighborhood size, cut weight, conductance — through a static-slot
scheduler: requests pack into a fixed
``--batch``-wide slot vector (static shapes ⇒ one compilation), mixed
query types route per-slot through one fused dispatch, and finished slots
refill from the queue each step. The JSON reports p50/p99 per-request
latency, QPS, and an order-independent sha256 digest of the answers — the
CI partitioned smoke compares it against the replicated tier's digest for
cross-process bit-identity.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import time

import jax
import numpy as np

from repro.core import SummaryConfig, summarize
from repro.core.queries_jax import (
    _SET_KINDS,
    KIND_CONDUCTANCE,
    KIND_CUT,
    KIND_KHOP,
    KIND_NAMES,
    PartitionedQueryEngine,
    QueryEngine,
    RoutedQueryEngine,
    pack_set_counts,
)
from repro.graphs import DATASETS, load_graph
from repro.launch.device import device_info, enable_compile_cache
from repro.runtime import make_mesh_from_plan, plan_mesh


@dataclasses.dataclass
class QueryRequest:
    rid: int
    kind: int       # KIND_* (repro.core.queries_jax)
    u: int = 0      # target node (degree/pagerank; row side of adjacency)
    v: int = 0      # second node (adjacency); hop count k (khop)
    a: np.ndarray | None = None  # node set A (cut/conductance)
    b: np.ndarray | None = None  # node set B (cut)
    answer: float | None = None
    t_submit: float = 0.0
    t_done: float = 0.0


class QueryServer:
    """Fixed-slot batch scheduler over a query engine.

    Queries are single-shot, so the continuous-batching loop degenerates
    nicely: every step admits up to ``slots`` requests from the queue into
    the fixed-shape slot vectors, answers them in one fused jitted
    dispatch, and frees every slot for the next step. Idle slots are
    padded with a degree probe of node 0 and masked out — the padded batch
    keeps the compiled shape, so a ragged final batch costs no
    recompilation (and, because slots are independent lanes of a
    vectorized kernel, answers cannot depend on batch packing —
    tests/test_query_serving.py pins this).
    """

    def __init__(self, engine, *, slots: int):
        self.engine = engine
        self.slots = slots
        self.queue: list[QueryRequest] = []
        self.done: list[QueryRequest] = []

    def submit(self, req: QueryRequest) -> None:
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    def step(self) -> bool:
        """Serve one batch. Returns False when the queue is drained."""
        if not self.queue:
            return False
        batch = [self.queue.pop(0) for _ in range(min(self.slots,
                                                      len(self.queue)))]
        kinds = np.zeros(self.slots, np.int32)
        u = np.zeros(self.slots, np.int32)
        v = np.zeros(self.slots, np.int32)
        for s, req in enumerate(batch):
            kinds[s], u[s], v[s] = req.kind, req.u, req.v
        if np.isin(kinds, _SET_KINDS).any():
            sets_a = [None] * self.slots
            sets_b = [None] * self.slots
            for s, req in enumerate(batch):
                sets_a[s], sets_b[s] = req.a, req.b
            ca, cb, ov = pack_set_counts(self.engine.bs, kinds,
                                         sets_a, sets_b)
            answers = self.engine.answer_batch(kinds, u, v, ca, cb, ov)
        else:
            answers = self.engine.answer_batch(kinds, u, v)
        t = time.perf_counter()
        for s, req in enumerate(batch):
            req.answer = float(answers[s])
            req.t_done = t
            self.done.append(req)
        return True


def random_workload(rng, v: int, n: int, kinds: list[int],
                    max_set: int | None = None,
                    k_max: int = 4) -> list[QueryRequest]:
    """A uniform mixed-kind request stream over random target nodes.

    Set kinds (cut/conductance) draw random node sets of up to
    ``max_set`` nodes (default v//4, at least 1); khop draws k in
    [0, ``k_max``] carried in the v lane."""
    max_set = max(1, v // 4) if max_set is None else max_set
    out = []
    for rid in range(n):
        kind = kinds[rid % len(kinds)]
        req = QueryRequest(rid=rid, kind=kind,
                           u=int(rng.integers(0, v)),
                           v=int(rng.integers(0, v)))
        if kind == KIND_KHOP:
            req.v = int(rng.integers(0, k_max + 1))
        elif kind in _SET_KINDS:
            req.a = rng.choice(v, size=int(rng.integers(1, max_set + 1)),
                               replace=False)
            if kind == KIND_CUT:
                req.b = rng.choice(
                    v, size=int(rng.integers(1, max_set + 1)),
                    replace=False)
        out.append(req)
    return out


def answers_digest(done: list[QueryRequest]) -> str:
    """Order-independent sha256 over (rid, float64 answer) pairs — equal
    digests ⇒ bit-identical answers for the same workload."""
    by_rid = sorted((r.rid, r.answer) for r in done)
    buf = np.array([[float(rid), float(ans)] for rid, ans in by_rid],
                   np.float64)
    return hashlib.sha256(buf.tobytes()).hexdigest()


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dataset", default="dblp", choices=sorted(DATASETS))
    ap.add_argument("--edge-list", default=None, metavar="PATH",
                    help="SNAP edge-list file; overrides --dataset/--scale")
    ap.add_argument("--chunk-edges", type=int, default=None)
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--k-frac", type=float, default=0.3)
    ap.add_argument("--T", type=int, default=10)
    ap.add_argument("--group-size", type=int, default=32)
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--batch", type=int, default=64,
                    help="slot count of the static-batch scheduler")
    ap.add_argument("--queries", default="degree,adjacency,pagerank",
                    help="comma-separated kinds to mix "
                         f"(of {sorted(KIND_NAMES)}); triangle is opt-in — "
                         "it is the one summary-space query that is not "
                         "O(1) per probe on large summaries")
    ap.add_argument("--distributed", action="store_true",
                    help="owner-routed engine over all local devices")
    ap.add_argument("--tier", default="replicated",
                    choices=("replicated", "partitioned"),
                    help="--distributed storage tier: replicated rows "
                         "(RoutedQueryEngine) or device-sharded rows with "
                         "halo exchange (PartitionedQueryEngine)")
    ap.add_argument("--dense-row-nnz", type=int, default=None,
                    help="partitioned tier: rows denser than this leave "
                         "the resident halo and use the second-hop route")
    ap.add_argument("--pagerank-iters", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    kind_names = [k.strip() for k in args.queries.split(",") if k.strip()]
    unknown = [k for k in kind_names if k not in KIND_NAMES]
    if unknown:
        ap.error(f"unknown query kind(s) {unknown}; "
                 f"expected from {sorted(KIND_NAMES)}")
    kinds = [KIND_NAMES[k] for k in kind_names]

    g = load_graph(args.edge_list or args.dataset,
                   chunk_edges=args.chunk_edges, scale=args.scale,
                   seed=args.seed)
    src, dst, v = np.asarray(g.src), np.asarray(g.dst), g.num_nodes
    cfg = SummaryConfig(T=args.T, k_frac=args.k_frac,
                        group_size=args.group_size, seed=args.seed)
    t0 = time.time()
    res = summarize(src, dst, v, cfg, collect_history=False)
    summarize_wall_s = time.time() - t0

    t0 = time.time()
    partition_stats = None
    if args.distributed:
        plan = plan_mesh(jax.device_count(), global_batch=1, want_model=1)
        mesh = make_mesh_from_plan(plan)
        if args.tier == "partitioned":
            engine = PartitionedQueryEngine(
                res, mesh, pagerank_iters=args.pagerank_iters,
                dense_row_nnz=args.dense_row_nnz)
            mode = f"partitioned{dict(mesh.shape)}"
            partition_stats = engine.partition_stats()
        else:
            engine = RoutedQueryEngine(res, mesh,
                                       pagerank_iters=args.pagerank_iters)
            mode = f"routed{dict(mesh.shape)}"
        owner_counts = engine.owner_counts().tolist()
    else:
        engine = QueryEngine(res, pagerank_iters=args.pagerank_iters)
        mode = "local"
        owner_counts = None
    build_wall_s = time.time() - t0

    rng = np.random.default_rng(args.seed)
    server = QueryServer(engine, slots=args.batch)
    # warmup: compile the fused dispatch (and any lazy global queries the
    # workload needs) outside the timed window
    warm = random_workload(rng, v, args.batch, kinds)
    for req in warm:
        server.submit(req)
    while server.step():
        pass
    server.done.clear()

    reqs = random_workload(rng, v, args.requests, kinds)
    t0 = time.perf_counter()
    for req in reqs:
        server.submit(req)
    while server.step():
        pass
    wall = time.perf_counter() - t0

    lat = np.array([r.t_done - r.t_submit for r in server.done])
    per_kind = {name: int(sum(r.kind == k for r in server.done))
                for name, k in KIND_NAMES.items() if k in kinds}
    result = {
        "dataset": args.edge_list or args.dataset,
        "V": v, "E": len(src),
        "num_supernodes": res.num_supernodes,
        "num_superedges": res.num_superedges,
        "mode": mode,
        "batch": args.batch,
        "requests": len(server.done),
        "queries": per_kind,
        "p50_latency_s": float(np.percentile(lat, 50)),
        "p99_latency_s": float(np.percentile(lat, 99)),
        "qps": len(server.done) / max(wall, 1e-9),
        "wall_s": wall,
        "summarize_wall_s": summarize_wall_s,
        "engine_build_wall_s": build_wall_s,
        "answers_digest": answers_digest(server.done),
        "source": g.source,
        "device": device_info(),
    }
    if owner_counts is not None:
        result["owner_counts"] = owner_counts
    if partition_stats is not None:
        result["partition_stats"] = partition_stats
    print(json.dumps(result, indent=1))
    return result


if __name__ == "__main__":
    main()
