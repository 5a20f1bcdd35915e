"""Out-of-core shard feeding: mmap'd CSR cache → per-device edge shards.

The paper's scalability claim (26× larger graphs, linear scaling) dies on
the host long before it dies on the accelerators if the driver re-packs
the full edge list into host arrays just to shard it. This module is the
zero-densify bridge from the binary CSR cache (:mod:`repro.graphs.io`,
DESIGN.md §10) to the edge-sharded shard_map pipeline
(:mod:`repro.core.distributed`, DESIGN.md §7): the mmap'd ``src``/``dst``
arrays are sliced into ``n_dev`` contiguous, ``-1``-padded shards, one
shard-sized staging buffer at a time — at no point is a full-|E| host
array materialized. See DESIGN.md §11 for the end-to-end data path
(file → sorted-run spill → CSR cache → per-shard feed → shard_map) and
its memory-model table.

Three entry points build the same sharded ``jax.Array`` pair:

* :func:`shard_edges_from_cache` — slices the cache's mmap'd ``.npy``
  members directly (peak host staging = one shard; the mmap'd pages are
  ``madvise(DONTNEED)``-ed after the feed, so even page-cache residency
  is transient);
* :func:`shard_edges` — in-memory fallback for edge lists that already
  live in host arrays (synthetic registry graphs); it subsumes the old
  ``pad_and_shard_edges`` and produces **bit-identical** shard contents,
  so the two paths are interchangeable down to the psum'd Eq.(2)/(4)
  metrics (asserted by ``tests/feed_check.py``);
* :func:`shard_edges_from_cache_multihost` — the process-spanning-mesh
  variant of the cache feed: each process stages only the shards its
  local devices own (DESIGN.md §15). The two single-process entry points
  refuse process-spanning meshes and point here.

Both fill each shard into the staging buffer, ``device_put`` it onto its
device, and assemble the global array with
``jax.make_array_from_single_device_arrays`` — the result is *born* with
the edge sharding (``MeshRules.edge_spec``), so
``jit``-ing the shard_map'd step never inserts a gather-and-reshard.
:class:`FeedStats` records the exact staging high-water mark; the CI
``ingest`` job asserts ``peak_staging_bytes`` never approaches 4·|E|.
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import numpy as np
from jax.sharding import NamedSharding

from repro.dist import make_rules
from repro.graphs import io as graph_io


@dataclasses.dataclass
class FeedStats:
    """Host-side accounting of one feed (``asdict`` lands in driver JSON).

    ``peak_staging_bytes`` is the high-water mark of host memory this
    module allocated to stage shards — by construction ≤ one shard
    (``shard_bytes``), never 4·|E|. ``bytes_copied`` counts what actually
    moved host→device (both columns, padding included).
    """

    num_edges: int = 0
    padded_edges: int = 0
    n_devices: int = 0
    shard_rows: int = 0
    shard_bytes: int = 0
    peak_staging_bytes: int = 0
    bytes_copied: int = 0
    path: str = "memory"  # "cache-mmap" | "memory" | "cache-mmap-multihost"
    process_count: int = 1
    local_shards: int = 0  # shards this process staged (== n_devices when 1 proc)

    def asdict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class EdgeShards:
    """Sharded padded edge columns plus provenance/accounting.

    ``src``/``dst`` are global ``jax.Array``s of shape ``[padded]``
    (``padded = |E| + (−|E| mod n_dev)``, ``-1`` in the padded slots),
    sharded contiguously over every mesh axis (``MeshRules.edge_spec``).
    """

    src: jax.Array
    dst: jax.Array
    num_edges: int  # unpadded |E|
    num_nodes: int | None  # from cache meta; None on the in-memory path
    stats: FeedStats


class ShardFeeder:
    """Staging allocator + accounting for the per-shard feed.

    Each shard is filled into a **fresh** buffer whose ownership passes to
    the jax runtime at ``device_put``. This is deliberate: PJRT's CPU
    client adopts suitably-aligned host buffers *zero-copy* (alignment-
    dependent, so nondeterministically), which means reusing one staging
    buffer in place would silently corrupt previously-fed shards — the
    regression test ``test_feeder_buffer_is_not_aliased_across_feeds``
    guards exactly that failure mode. Accelerator backends copy to device
    memory and the staging buffer is freed at the next allocation. Either
    way, at most one *transient* staging shard is ever alive beyond the
    device-owned data; ``peak_staging_bytes`` is the feeder-lifetime
    high-water mark of a single staging allocation (shared feeders — e.g.
    one per benchmark sweep — accumulate their max across feeds).
    """

    def __init__(self) -> None:
        self.peak_staging_bytes = 0

    def staging(self, rows: int, stats: "FeedStats | None" = None,
                ) -> np.ndarray:
        """Allocate one staging shard — the single accounting site: the
        feeder-lifetime and per-feed high-water marks are both recorded
        here so they cannot drift apart."""
        buf = np.empty((rows,), np.int32)
        self.peak_staging_bytes = max(self.peak_staging_bytes, buf.nbytes)
        if stats is not None:
            stats.peak_staging_bytes = max(stats.peak_staging_bytes,
                                           buf.nbytes)
        return buf


def shard_layout(num_edges: int, n_dev: int) -> tuple[int, int]:
    """``(rows_per_shard, padded_total)`` for ``num_edges`` over ``n_dev``.

    Matches the historical ``pad_and_shard_edges`` padding exactly
    (``padded = |E| + (−|E| mod n_dev)``), so shard contents — and hence
    every downstream psum'd metric — are bit-identical across paths.
    When ``n_dev ∤ |E|`` the last shard is part padding; when
    ``|E| < n_dev`` trailing shards are *all* padding (``-1`` rows, which
    ``_local_pairs`` already masks out).
    """
    if n_dev <= 0:
        raise ValueError(f"n_dev must be positive, got {n_dev}")
    padded = num_edges + (-num_edges) % n_dev
    return padded // n_dev, padded


def _edge_sharding(mesh) -> tuple[NamedSharding, int]:
    rules = make_rules(mesh)
    return NamedSharding(mesh, rules.edge_spec), rules.n_devices


def mesh_process_count(mesh) -> int:
    """Number of OS processes the mesh's devices live in."""
    return len({d.process_index for d in np.asarray(mesh.devices).ravel()})


def _require_single_process(mesh, entry: str) -> None:
    """The single-process feeds stage EVERY shard locally — on a
    process-spanning mesh that is both wrong (``device_put`` onto a
    non-addressable device fails) and, were it patched naively, would
    re-stage the full |E| on every host. Fail loudly and point at the
    multi-host entry point instead of letting jax produce an opaque
    cross-process placement error."""
    n_proc = mesh_process_count(mesh)
    if n_proc > 1:
        raise RuntimeError(
            f"{entry} feeds every shard from one host and cannot run on a "
            f"mesh spanning {n_proc} processes; use "
            f"repro.graphs.feed.shard_edges_from_cache_multihost, which "
            f"stages only the shards addressable by this process "
            f"(DESIGN.md §15)")


def _madvise_dontneed(column) -> None:
    """Drop the resident pages of an mmap'd column (best-effort)."""
    try:
        import mmap as _mmap

        column._mmap.madvise(_mmap.MADV_DONTNEED)  # noqa: SLF001
    except (AttributeError, ValueError, OSError):
        pass


def _feed_column(column, num_edges: int, sharding, padded: int,
                 feeder: ShardFeeder, stats: FeedStats,
                 addressable_only: bool = False) -> jax.Array:
    """Slice one edge column into per-device shards through the feeder.

    ``column`` may be an ``np.memmap`` (cache path — each slice is one
    page-streamed memcpy into staging) or a plain ndarray (memory path).
    With ``addressable_only`` the loop visits only the devices owned by
    *this* process (``addressable_devices_indices_map``), so each host
    stages — and mmap-touches — only its own slice of the columns;
    ``make_array_from_single_device_arrays`` assembles the global array
    from every process's addressable shards (DESIGN.md §15).
    """
    shape = (padded,)
    singles = []
    if addressable_only:
        index_map = sharding.addressable_devices_indices_map(shape)
    else:
        index_map = sharding.devices_indices_map(shape)
    for dev, idx in index_map.items():
        sl = idx[0]
        a = 0 if sl.start is None else int(sl.start)
        b = padded if sl.stop is None else int(sl.stop)
        buf = feeder.staging(b - a, stats)
        n_valid = max(min(num_edges, b) - a, 0)
        if n_valid:
            np.copyto(buf[:n_valid], column[a:a + n_valid],
                      casting="same_kind")
        if n_valid < b - a:
            buf[n_valid:] = -1
        # ownership of ``buf`` passes to the runtime here (PJRT CPU may
        # adopt it zero-copy) — it must never be written again
        singles.append(jax.device_put(buf, dev))
        stats.bytes_copied += buf.nbytes
        del buf
    return jax.make_array_from_single_device_arrays(shape, sharding, singles)


def _feed(src, dst, num_edges: int, mesh, feeder: ShardFeeder | None,
          path: str, num_nodes: int | None,
          addressable_only: bool = False) -> EdgeShards:
    sharding, n_dev = _edge_sharding(mesh)
    shard_rows, padded = shard_layout(num_edges, n_dev)
    feeder = feeder or ShardFeeder()
    stats = FeedStats(num_edges=num_edges, padded_edges=padded,
                      n_devices=n_dev, shard_rows=shard_rows,
                      shard_bytes=shard_rows * 4, path=path,
                      process_count=mesh_process_count(mesh))
    src_g = _feed_column(src, num_edges, sharding, padded, feeder, stats,
                         addressable_only)
    dst_g = _feed_column(dst, num_edges, sharding, padded, feeder, stats,
                         addressable_only)
    stats.local_shards = len(src_g.addressable_shards)
    return EdgeShards(src=src_g, dst=dst_g, num_edges=num_edges,
                      num_nodes=num_nodes, stats=stats)


def shard_edges(src, dst, mesh, *, feeder: ShardFeeder | None = None,
                ) -> EdgeShards:
    """In-memory fallback: shard a canonical edge list already in host RAM.

    Subsumes the old ``pad_and_shard_edges``: same ``-1`` padding, same
    contiguous placement, but built shard-by-shard through the feeder's
    staging buffer instead of a full-length ``np.concatenate`` copy — and
    the result is committed to its final edge sharding, so ``jit`` never
    re-gathers it. Inputs must already be canonical (``src < dst``,
    unique — ``repro.core.types.make_graph`` output or a cache column).
    """
    _require_single_process(mesh, "shard_edges")
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    if src.shape != dst.shape or src.ndim != 1:
        raise ValueError(f"edge columns must be equal-length 1-D arrays; "
                         f"got {src.shape} vs {dst.shape}")
    return _feed(src, dst, int(src.shape[0]), mesh, feeder, "memory", None)


def shard_edges_from_cache(cache_dir: str, mesh, *,
                           feeder: ShardFeeder | None = None) -> EdgeShards:
    """Feed the binary CSR cache straight onto the mesh, zero-densify.

    Opens the cache's ``src.npy``/``dst.npy`` with ``mmap_mode="r"`` and
    slices them per shard — peak host RSS is one staging shard
    (``FeedStats.shard_bytes``) plus transiently-resident mmap pages,
    which are ``madvise(DONTNEED)``-ed after each column. ``|E|``/``|V|``
    come from ``meta.json``, so nothing is scanned. Raises
    ``FileNotFoundError`` when the cache is missing members or stale
    (``repro.graphs.io.cache_is_fresh``) — callers should re-ingest via
    :func:`repro.graphs.io.load_graph` first.
    """
    _require_single_process(mesh, "shard_edges_from_cache")
    return _feed_cache(cache_dir, mesh, feeder, "cache-mmap",
                       addressable_only=False)


def shard_edges_from_cache_multihost(cache_dir: str, mesh, *,
                                     feeder: ShardFeeder | None = None,
                                     ) -> EdgeShards:
    """Multi-host cache feed: each process stages ONLY its local shards.

    Every participating process calls this with the same ``cache_dir``
    (shared filesystem or an identical local copy) and the same
    process-spanning mesh, after :func:`repro.launch.mesh.
    bootstrap_distributed`. Each process mmaps the cache, slices out just
    the rows its *addressable* devices own, and the global array is
    assembled from the per-process shards — no host ever materializes (or
    even pages in) a full-|E| array, so per-host peak RSS stays at one
    staging shard regardless of process count (DESIGN.md §15; the CI
    ``multihost`` job asserts the RSS budget). Shard layout and padding
    are identical to :func:`shard_edges_from_cache`, so the summary — and
    the launcher JSON — is bit-identical to the single-process run on the
    same global device count. Also valid on a single-process mesh, where
    "addressable" means "all" and it degenerates to the cache feed.
    """
    return _feed_cache(cache_dir, mesh, feeder,
                       "cache-mmap-multihost" if mesh_process_count(mesh) > 1
                       else "cache-mmap",
                       addressable_only=True)


def _feed_cache(cache_dir: str, mesh, feeder: ShardFeeder | None,
                path: str, *, addressable_only: bool) -> EdgeShards:
    if not graph_io.cache_is_fresh(cache_dir):
        raise FileNotFoundError(
            f"{cache_dir!r}: not a complete ssumm cache "
            f"(missing/corrupt members or stale meta.json); "
            f"re-ingest with repro.graphs.io.load_graph")
    with open(os.path.join(cache_dir, "meta.json")) as f:
        meta = json.load(f)
    num_edges = int(meta["num_edges"])
    src_mm = np.load(os.path.join(cache_dir, "src.npy"), mmap_mode="r")
    dst_mm = np.load(os.path.join(cache_dir, "dst.npy"), mmap_mode="r")
    if src_mm.shape[0] != num_edges or dst_mm.shape[0] != num_edges:
        raise ValueError(
            f"{cache_dir!r}: meta.json says |E|={num_edges} but members "
            f"have {src_mm.shape[0]}/{dst_mm.shape[0]} rows")
    out = _feed(src_mm, dst_mm, num_edges, mesh, feeder, path,
                int(meta["num_nodes"]), addressable_only)
    _madvise_dontneed(src_mm)
    _madvise_dontneed(dst_mm)
    return out
