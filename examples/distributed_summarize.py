"""Distributed SSumM: edge-sharded summarization under shard_map.

    python examples/distributed_summarize.py      # no PYTHONPATH needed

Spawns 8 placeholder host devices, shards the edge list over a (2, 4) mesh, and runs the
paper's iteration loop through the SummaryEngine (DESIGN.md §12) over the
edge-sharded DistributedBackend: all_to_all pair-exchange + owner-local
merge rounds, with up to ``cfg.driver_chunk`` rounds per device dispatch
(lax.while_loop inside the shard_map body). The replicated partition and
the global metrics match the single-device path (see tests/dist_check.py
for the exact-parity assertions).
"""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.core import SummaryConfig
from repro.core.distributed import make_distributed_backend
from repro.core.engine import SummaryEngine
from repro.core.types import make_graph
from repro.graphs import generate
from repro.graphs.feed import shard_edges
from repro.launch.mesh import make_host_mesh


def main():
    src, dst, v = generate("dblp", seed=0, scale=0.02)
    graph, _ = make_graph(src, dst, v)
    e = graph.num_edges
    size_g = 2.0 * e * np.log2(max(v, 2))
    print(f"graph: |V|={v} |E|={e}  Size(G)={size_g:,.0f} bits")
    print(f"devices: {jax.device_count()} → mesh (2, 4) = (data, model)")

    mesh = make_host_mesh((2, 4), ("data", "model"))
    cfg = SummaryConfig(T=10, k_frac=0.3)
    # compact group-owner sharding (the web-scale path, DESIGN.md §7);
    # small graphs need a generous routing capacity (few groups → skew)
    backend = make_distributed_backend(mesh, cfg, v, e, grouping="compact",
                                       capacity_factor=32.0, lean_sort=True)
    # per-shard feed (DESIGN.md §11): shards are born on their devices;
    # real graphs would come off the mmap'd CSR cache the same way via
    # shard_edges_from_cache(cache_dir, mesh) — zero host densify
    shards = shard_edges(np.asarray(graph.src), np.asarray(graph.dst), mesh)
    print(f"edge shard per device: {shards.stats.shard_rows} edges "
          f"(host staging {shards.stats.peak_staging_bytes} B — one shard)")

    # the engine owns Alg. 1: θ schedule, stopping rule, chunked driver,
    # and the Sect. 3.2.4 drop-to-k finalize (edge-sharded ξ-th order
    # statistic — no host-side gather; DESIGN.md §7/§12)
    run = SummaryEngine(backend.bind(shards.src, shards.dst)).run()
    k_bits = run.k_bits
    for row in run.history:
        print(f"  t={int(row['t']):2d} θ={row['theta']:.2f} "
              f"|S|={int(row['num_supernodes']):5d} "
              f"size={row['size_bits']:12,.0f} bits "
              f"({100 * row['size_bits'] / size_g:5.1f}%) "
              f"merges={int(row['nmerges']):4d} "
              f"overflow={int(row['overflow'])}")
    if run.last_stats and run.last_stats["size_bits"] <= k_bits:
        print("  budget reached")

    sp_stats = run.finalize["stats"]
    print(f"sparsify: ξ={int(float(sp_stats['xi']))} "
          f"dropped={int(float(sp_stats['dropped']))} superedges → "
          f"size={float(sp_stats['size_bits']):12,.0f} bits "
          f"({100 * float(sp_stats['size_bits']) / size_g:5.1f}%) "
          f"RE₁={float(sp_stats['re1']):.4f}")


if __name__ == "__main__":
    main()
