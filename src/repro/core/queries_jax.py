"""Batched, device-resident summary-query engine (DESIGN.md §14).

The numpy functions in :mod:`repro.core.queries` answer one query at a
time on the host. This module serves the same block-space math at
interactive traffic: the :class:`~repro.core.queries.BlockSummary` CSR is
put on device once (float64 via ``jax.enable_x64`` — queries are
read-mostly and tiny next to the summary, so full precision is free) and
every query kernel is jitted and vectorized over a ``[B]`` request batch:

  * ``expected_degree``  — one gather: ``deg[node2block[u]]``;
  * ``adjacency_weight`` — O(log nnz) lookup of σ via ``searchsorted`` on
    the globally-sorted ``row·S + col`` key;
  * ``pagerank``         — block-space power iteration as a
    ``lax.while_loop`` (computed once, then served as a gather), mirroring
    :func:`repro.core.queries.pagerank_blocks` update-for-update including
    the early tolerance break;
  * ``triangle_density`` — inner wedge sums per CSR entry (work nnz·D,
    chunked with ``lax.map``), then planned per-row sums;
  * ``cut_weight`` / ``conductance`` — node sets packed to per-block count
    rows on the host, reduced as per-row cut contributions;
  * ``k_hop_size`` — BFS fixpoint on superedge support in block space
    (exact for the block-constant Ĝ), one segmented OR over the entries
    per step.

Every kernel sums each CSR row in a fixed order that depends on the row
alone (:func:`row_sum_plan` over flat entries, :func:`row_sum` over one
row's ``[D]``-wide view), so per-row values are bit-identical between the
single-device :class:`QueryEngine` and the owner-routed
:class:`RoutedQueryEngine`: the routed engine masks each row/query to the
device owning its supernode (``MeshRules.owner`` — the same hash that
routes the distributed merge step's pair exchange) and merges with a
``psum`` of disjoint one-hot contributions, which is exact in floating
point (one real value plus zeros). This is the shard-routing tier of SNIPPETS Snippet 3's fan-out →
owner-routed progression: *compute* is routed per owner, the summary
arrays themselves are still replicated per device.

:class:`PartitionedQueryEngine` is the second, memory-partitioned tier
(DESIGN.md §16): each device holds only the entries of its owned rows
plus precomputed halo tables; cross-device lookups go through a per-step
all-gather of the owned value slab (PageRank shares) or resident halo row
copies (triangle wedges), with a second-hop all-gather fallback for rows
denser than ``dense_row_nnz``. Answers stay bit-identical to both
replicated tiers because per-row reductions and their merge order are
unchanged — only row *storage* moves.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from repro.core.queries import BlockSummary, build_block_summary
from repro.core.types import SummaryResult
from repro.dist import make_rules

# float64 scope for every device-side query op (jax's thread-local x64 flag)
enable_x64 = functools.partial(jax.enable_x64, True)

# Query kinds of the serving wire format (int32 per slot).
KIND_DEGREE = 0
KIND_ADJACENCY = 1
KIND_PAGERANK = 2
KIND_TRIANGLE = 3
KIND_KHOP = 4          # u = target node, v = hop count k
KIND_CUT = 5           # node sets A/B arrive as per-block count rows
KIND_CONDUCTANCE = 6   # node set A as count row; complement derived
KIND_NAMES = {
    "degree": KIND_DEGREE,
    "adjacency": KIND_ADJACENCY,
    "pagerank": KIND_PAGERANK,
    "triangle": KIND_TRIANGLE,
    "khop": KIND_KHOP,
    "cut": KIND_CUT,
    "conductance": KIND_CONDUCTANCE,
}
# kinds with no per-node target: answered by (routed to) device 0
_GLOBAL_KINDS = (KIND_TRIANGLE, KIND_CUT, KIND_CONDUCTANCE)
# kinds dispatched through the extended analytics kernel (set counts /
# BFS inputs) rather than the point-query fast path
_ANALYTIC_KINDS = (KIND_KHOP, KIND_CUT, KIND_CONDUCTANCE)
# kinds whose requests carry node sets (packed to count rows on the host)
_SET_KINDS = (KIND_CUT, KIND_CONDUCTANCE)


#: Entries added per step of a per-row sum (a power of two): most rows of a
#: power-law summary hold a handful of entries.
ROW_SUM_WIDTH = 4


def row_sum_plan(lengths):
    """Host-built gather plan for per-row sums over row-major entries.

    Each level adds groups of ``ROW_SUM_WIDTH`` consecutive items of a row
    (a fixed pairwise tree, zero-padded) until every row has one item
    left, so a row's sum is associated by its own length alone: the
    replicated and partitioned tiers, whatever rows they hold, add it
    identically, in about nnz + S·ROW_SUM_WIDTH gathered values. Returns
    ``(levels, finished)``: per level ``(idx [G, ROW_SUM_WIDTH]`` into the
    level's items, -1 for zero; ``fin``, the groups whose row finishes
    there; ``cont``, the groups carried on) and, per level, the rows
    finishing there."""
    width = ROW_SUM_WIDTH
    length = np.asarray(lengths, np.int64)
    alive = np.arange(length.size)
    start = np.cumsum(length) - length
    levels, finished = [], []
    while True:
        g = np.maximum(1, -(-length // width))
        gstart = np.cumsum(g) - g
        grp = np.repeat(np.arange(alive.size), g)
        off = ((np.arange(grp.size) - gstart[grp])[:, None] * width
               + np.arange(width)[None, :])
        idx = np.where(off < length[grp][:, None],
                       start[grp][:, None] + off, -1)
        one = g == 1
        levels.append((idx.astype(np.int32),
                       gstart[one].astype(np.int32),
                       np.flatnonzero(~one[grp]).astype(np.int32)))
        finished.append(alive[one])
        if one.all():
            return levels, finished
        alive, length = alive[~one], g[~one]
        start = np.cumsum(length) - length


def row_sum_plans(lengths):
    """One :func:`row_sum_plan` per row set (``lengths`` [P, rows]), padded
    to common shapes and stacked on a leading axis, plus each row's place
    among its set's finished groups (``order`` [P, rows]). Padding adds
    only all-zero groups, so no row's value changes. Returns host arrays
    ``(levels, order)``; one set's plan is ``x[q]`` of every leaf."""
    plans = [row_sum_plan(row_lengths) for row_lengths in lengths]
    depth = max(len(levels) for levels, _ in plans)
    levels, fin_sizes = [], []
    for k in range(depth):
        lv = [p[0][k] if k < len(p[0]) else None for p in plans]
        n_g, n_f, n_c = (max([1] + [x[i].shape[0] for x in lv if x is not None])
                         for i in range(3))
        idx = np.full((len(plans), n_g, ROW_SUM_WIDTH), -1, np.int32)
        fin = np.zeros((len(plans), n_f), np.int32)
        cont = np.zeros((len(plans), n_c), np.int32)
        for q, x in enumerate(lv):
            if x is not None:
                idx[q, :x[0].shape[0]] = x[0]
                fin[q, :x[1].size] = x[1]
                cont[q, :x[2].size] = x[2]
        levels.append((idx, fin, cont))
        fin_sizes.append(n_f)
    order = np.zeros((len(plans), len(lengths[0])), np.int32)
    for q, (_, finished) in enumerate(plans):
        offset = 0
        for rows, size in zip(finished, fin_sizes):
            order[q, rows] = offset + np.arange(rows.size)
            offset += size
    return levels, order


def planned_row_sums(plan, vals: jax.Array) -> jax.Array:
    """Σ per row of ``vals`` (``[N, ...]`` in row-major entry order) along
    one set's :func:`row_sum_plans`. The addends pass an optimization
    barrier: a product fused into the add could become a multiply-add that
    rounds once in one tier's program and twice in another's."""
    levels, order = plan
    items, done = vals, []
    for idx, fin, cont in levels:
        ext = jnp.concatenate(
            [items, jnp.zeros((1,) + items.shape[1:], items.dtype)])
        x = jax.lax.optimization_barrier(ext[idx])
        while x.shape[1] > 1:
            half = x.shape[1] // 2
            x = x[:, :half] + x[:, half:]
        out = x[:, 0]
        done.append(out[fin])
        items = out[cont]
    return jnp.concatenate(done)[order]


@dataclasses.dataclass(frozen=True)
class DeviceBlocks:
    """The BlockSummary CSR on device (float64), plus static shape meta.

    The entries stay flat (nnz of them): a summary of a power-law graph has
    a few rows of width D and very many short ones, so a padded ``[S, D]``
    layout is almost all padding. ``key = row·S + col`` over the entries is
    globally sorted (CSR rows and columns both sorted), enabling
    binary-search pair lookups; ``rows``/``cols`` are the same entries
    unpacked and ``indptr`` gives one row's entries as a ``[D]``-wide view
    (:func:`row_views`).
    """

    node2block: jax.Array  # int32[V]
    sizes: jax.Array       # float64[S]
    deg: jax.Array         # float64[S]
    key: jax.Array         # int64[nnz] sorted row·S + col
    sigma: jax.Array       # float64[nnz] (key order)
    degw: jax.Array        # float64[nnz] (key order)
    rows: jax.Array        # int32[nnz] (key order)
    cols: jax.Array        # int32[nnz] (key order)
    indptr: jax.Array      # int32[S+1] CSR row pointers
    plan: tuple            # row_sum_plan of the S rows (int32 arrays)
    s: int                 # static |S|
    d: int                 # static widest row
    nnz: int               # static superedge-entry count
    num_nodes: int         # static |V|


jax.tree_util.register_pytree_node(
    DeviceBlocks,
    lambda b: ((b.node2block, b.sizes, b.deg, b.key, b.sigma, b.degw,
                b.rows, b.cols, b.indptr, b.plan),
               (b.s, b.d, b.nnz, b.num_nodes)),
    lambda meta, leaves: DeviceBlocks(*leaves, *meta),
)


#: :class:`DeviceBlocks` leaves that hold the rows; the other leaves are the
#: O(V)/O(S) metadata the partitioned tier replicates as well.
ROW_LEAVES = ("key", "sigma", "degw", "rows", "cols", "indptr", "plan")


def host_blocks(bs: BlockSummary) -> dict:
    """The :class:`DeviceBlocks` leaves of ``bs`` as host numpy arrays."""
    rows = bs.rows.astype(np.int64)
    levels, order = row_sum_plans([np.diff(bs.indptr)])
    return dict(
        node2block=bs.node2block.astype(np.int32),
        sizes=bs.sizes.astype(np.float64),
        deg=bs.deg.astype(np.float64),
        key=rows * bs.num_blocks + bs.cols,
        sigma=bs.sigma.astype(np.float64),
        degw=bs.deg_w.astype(np.float64),
        rows=rows.astype(np.int32),
        cols=bs.cols.astype(np.int32),
        indptr=bs.indptr.astype(np.int32),
        plan=jax.tree_util.tree_map(lambda x: x[0], (levels, order)),
    )


def device_blocks(bs: BlockSummary) -> DeviceBlocks:
    """Put a host BlockSummary on device (call under ``enable_x64``)."""
    leaves = jax.tree_util.tree_map(jnp.asarray, host_blocks(bs))
    return DeviceBlocks(**leaves, s=bs.num_blocks,
                        d=max(1, bs.max_row_nnz()), nnz=bs.nnz,
                        num_nodes=bs.num_nodes)


# --------------------------------------------------------------- kernels
# Pure functions of (DeviceBlocks, batch arrays); shared verbatim by the
# single-device and routed engines so per-row/per-query float values are
# identical on both paths. Per-row sums follow ``dev.plan``
# (:func:`planned_row_sums`), as the partitioned tier's do for its rows.

def row_sum(x: jax.Array) -> jax.Array:
    """Σ over the last (padded-row) axis in one fixed pairwise order.

    ``jnp.sum`` leaves the order to the compiler, which may pick
    differently for different array shapes; halving a zero-padded
    power-of-two width adds a row's entries identically in every tier."""
    x = jax.lax.optimization_barrier(x)
    d = x.shape[-1]
    width = 1 << max(d - 1, 0).bit_length()
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - d)])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def degree_kernel(dev: DeviceBlocks, u: jax.Array) -> jax.Array:
    return dev.deg[dev.node2block[u]]


def adjacency_kernel(dev: DeviceBlocks, u: jax.Array,
                     v: jax.Array) -> jax.Array:
    if dev.nnz == 0:
        return jnp.zeros(u.shape, jnp.float64)
    a = dev.node2block[u].astype(jnp.int64)
    b = dev.node2block[v].astype(jnp.int64)
    qk = a * dev.s + b
    pos = jnp.clip(jnp.searchsorted(dev.key, qk), 0, dev.nnz - 1)
    sig = jnp.where(dev.key[pos] == qk, dev.sigma[pos], 0.0)
    return jnp.where(u == v, 0.0, sig)


def pagerank_row_sums(dev: DeviceBlocks, share: jax.Array) -> jax.Array:
    """Σ_e∈row deg_w[e]·share[col(e)] for every row — the power-step row
    reduction."""
    return planned_row_sums(dev.plan, dev.degw * share[dev.cols])


def damped(p_full, new_rows, deg, sizes, damping: float, vt: float):
    """(1-d)/V + d·(row sums + dangling mass/V), every tier's PageRank step.

    Products pass an optimization barrier before they are added, so no
    tier's program can fuse one into a multiply-add that rounds once."""
    barrier = jax.lax.optimization_barrier
    dangling = jnp.sum(barrier(jnp.where(deg <= 0, p_full * sizes, 0.0)))
    return (1.0 - damping) / vt + barrier(damping * (new_rows
                                                     + dangling / vt))


def pagerank_update(dev: DeviceBlocks, p: jax.Array, new_rows: jax.Array,
                    damping: float) -> tuple[jax.Array, jax.Array]:
    """Damping + dangling redistribution + tolerance residual (replicated
    math: identical on every device from replicated ``p``/``new_rows``)."""
    new = damped(p, new_rows, dev.deg, dev.sizes, damping,
                 float(dev.num_nodes))
    return new, jnp.max(jnp.abs(new - p))


def wedge_inner(a, b, cols_b, sig_b, sizes, sigma_ca) -> jax.Array:
    """Σ_{c∈row b, c>b} σ_bc σ_ca n_c for a batch of entries (a, b), zero
    unless b > a. ``cols_b``/``sig_b`` ([R, D]) are row b's entries
    and ``sigma_ca(c)`` looks σ_ca up (0 where absent). Every tier reduces
    the same view of row b, so the inner sums are bit-identical."""
    s = sizes.shape[0]
    mask_c = (cols_b >= 0) & (cols_b > b[:, None]) & (b > a)[:, None]
    c = jnp.clip(cols_b, 0, s - 1)
    sca = jnp.where(mask_c, sigma_ca(c), 0.0)
    return row_sum(jnp.where(mask_c, sig_b * sca * sizes[c], 0.0))


def row_views(start, end, cols, sigma, r: jax.Array, d: int):
    """Rows ``r`` ([R]) of flat entries as ``[R, d]`` views ``(cols,
    sigma)``: row i holds entries ``start[i]:end[i]``, padded with -1 / 0
    past its end."""
    pos = start[r][:, None] + jnp.arange(d)[None, :]
    live = pos < end[r][:, None]
    pos = jnp.clip(pos, 0, cols.shape[0] - 1)
    return (jnp.where(live, cols[pos], -1),
            jnp.where(live, sigma[pos], 0.0))


def triangle_weights(a, b, sig, inner, sizes) -> jax.Array:
    """Per entry (a, b): σ_ab n_a n_b · inner(a, b) where b > a, else 0;
    their row sums are tri[a]."""
    s = sizes.shape[0]
    return jnp.where(b > a, sig * inner * sizes[jnp.clip(a, 0, s - 1)]
                     * sizes[jnp.clip(b, 0, s - 1)], 0.0)


def triangle_rows(dev: DeviceBlocks, entry_chunk: int) -> jax.Array:
    """Per-row triangle mass tri[a] = Σ_{b>a} σ_ab n_a n_b Σ_{c>b} σ_bc
    σ_ca n_c (float64[S]); total = tri.sum(). The inner sums run over the
    CSR entries (a, b), ``entry_chunk`` at a time, each over row b's
    ``[D]``-wide view, so the work is nnz·D rather than S·D²; chunking
    never changes a value."""
    s, nnz = dev.s, dev.nnz
    if nnz == 0:
        return jnp.zeros((s,), jnp.float64)
    chunk = max(1, min(entry_chunk, nnz))
    n_chunks = -(-nnz // chunk)
    ids = jnp.arange(n_chunks * chunk, dtype=jnp.int32).reshape(n_chunks,
                                                                chunk)

    def one_chunk(idx):
        e = jnp.minimum(idx, nnz - 1)
        a, b = dev.rows[e], dev.cols[e]

        def sigma_ca(c):
            qk = c.astype(jnp.int64) * s + a[:, None].astype(jnp.int64)
            pos = jnp.clip(jnp.searchsorted(dev.key, qk.ravel()),
                           0, nnz - 1).reshape(qk.shape)
            return jnp.where(dev.key[pos] == qk, dev.sigma[pos], 0.0)

        cols_b, sig_b = row_views(dev.indptr[:-1], dev.indptr[1:], dev.cols,
                                  dev.sigma, b, dev.d)
        return wedge_inner(a, b, cols_b, sig_b, dev.sizes, sigma_ca)

    inner = jax.lax.map(one_chunk, ids).reshape(-1)[:nnz]
    w = triangle_weights(dev.rows, dev.cols, dev.sigma, inner, dev.sizes)
    return planned_row_sums(dev.plan, w)


def answer_kernel(dev: DeviceBlocks, kinds, u, v, pr_blocks, tri) -> jax.Array:
    """One fused batched dispatch: per-slot answer selected by kind."""
    deg = degree_kernel(dev, u)
    adj = adjacency_kernel(dev, u, v)
    prq = pr_blocks[dev.node2block[u]]
    tri_b = jnp.broadcast_to(tri, kinds.shape)
    return jnp.select(
        [kinds == KIND_DEGREE, kinds == KIND_ADJACENCY,
         kinds == KIND_PAGERANK, kinds == KIND_TRIANGLE],
        [deg, adj, prq, tri_b], 0.0)


def pack_set_counts(bs: BlockSummary, kinds, sets_a, sets_b):
    """Host-side packing of node-set queries to per-block count rows.

    ``sets_a``/``sets_b`` are length-B sequences (entries for non-set
    kinds are ignored; may be None). Returns float64 ``(cnt_a, cnt_b, ov)``
    of shape [B, S]: A-counts, B-counts and |A∩B|-counts per block — the
    same ``Q.block_counts`` dedup semantics as the numpy reference, so the
    jitted kernels see identical inputs.
    """
    kinds = np.asarray(kinds, np.int32)
    b, s = kinds.shape[0], bs.num_blocks
    cnt_a = np.zeros((b, s), np.float64)
    cnt_b = np.zeros((b, s), np.float64)
    ov = np.zeros((b, s), np.float64)

    def counts(nodes):
        out = np.zeros(s, np.float64)
        nodes = np.unique(np.asarray(nodes, dtype=np.int64))
        if nodes.size:
            np.add.at(out, bs.node2block[nodes], 1.0)
        return out, nodes

    for i, k in enumerate(kinds):
        if k not in _SET_KINDS:
            continue
        a = sets_a[i] if sets_a is not None and sets_a[i] is not None else ()
        cnt_a[i], a_u = counts(a)
        if k == KIND_CUT:
            bb = (sets_b[i]
                  if sets_b is not None and sets_b[i] is not None else ())
            cnt_b[i], b_u = counts(bb)
            ov[i], _ = counts(np.intersect1d(a_u, b_u, assume_unique=True))
    return cnt_a, cnt_b, ov


def cut_rows(dev: DeviceBlocks, c_a, c_b, ov) -> jax.Array:
    """Per-row cut contributions [B, S] from count rows [B, S].

    Row a contributes ``c_a[a]·Σ_j σ_aj·c_b[col_j] − σ_aa·ov[a]`` — summing
    over rows reproduces the numpy ``_cut_from_counts`` value."""
    sdiag = planned_row_sums(
        dev.plan, jnp.where(dev.cols == dev.rows, dev.sigma, 0.0))
    rowsum = planned_row_sums(dev.plan, dev.sigma[:, None] * c_b.T[dev.cols])
    return c_a * rowsum.T - sdiag[None, :] * ov


def khop_step_rows(dev: DeviceBlocks, reach) -> jax.Array:
    """One BFS step on superedge support: row a becomes reachable when any
    neighbor with σ > 0 is in ``reach`` (bool [B, S] → bool [B, S]),
    counted over the CSR entries."""
    hit = reach[:, dev.cols] & (dev.sigma > 0)[None, :]     # [B, nnz]
    return planned_row_sums(dev.plan, hit.T.astype(jnp.int32)).T > 0


def analytics_answers(sizes, deg, a0, kinds, kvec, cnt_a, cnt_b, ov,
                      cut_rows_fn, khop_step_fn, khop_max: int):
    """(khop, cut, conductance) float64[B] from per-row callbacks.

    All post-row math (volumes, the BFS fixpoint loop, the member sums)
    operates on replicated [B, S]/[S] arrays in one canonical order, so as
    long as ``cut_rows_fn``/``khop_step_fn`` return the same per-row floats
    the three tiers agree bitwise. ``kvec`` carries k for khop slots;
    conductance derives its complement counts from ``cnt_a`` internally.
    """
    s = sizes.shape[0]
    is_cond = kinds == KIND_CONDUCTANCE
    cb_eff = jnp.where(is_cond[:, None], sizes[None, :] - cnt_a, cnt_b)
    ov_eff = jnp.where(is_cond[:, None], 0.0, ov)
    crows = cut_rows_fn(cnt_a, cb_eff, ov_eff)
    cut = jnp.sum(crows, axis=-1)
    vol_a = jnp.sum(cnt_a * deg[None, :], axis=-1)
    vol_c = jnp.sum((sizes[None, :] - cnt_a) * deg[None, :], axis=-1)
    denom = jnp.minimum(vol_a, vol_c)
    cond = jnp.where(denom > 0.0,
                     cut / jnp.where(denom > 0.0, denom, 1.0), 0.0)

    onehot = a0[:, None] == jnp.arange(s)[None, :]

    def body(t, r):
        inp = jnp.where(t == 0, onehot, r)
        nxt = khop_step_fn(inp) | r
        return jnp.where((t < kvec)[:, None], nxt, r)

    reach = jax.lax.fori_loop(0, khop_max, body, jnp.zeros_like(onehot))
    members = sizes[None, :] - onehot.astype(jnp.float64)
    khop = 1.0 + jnp.sum(jnp.where(reach, members, 0.0), axis=-1)
    return khop, cut, cond


def answer_kernel_full(dev: DeviceBlocks, kinds, u, v, pr_blocks, tri,
                       cnt_a, cnt_b, ov, khop_max: int,
                       cut_rows_fn=None, khop_step_fn=None) -> jax.Array:
    """The fused dispatch extended with the analytics kinds (khop carries
    k in the v lane; cut/conductance read the [B, S] count rows)."""
    base = answer_kernel(dev, kinds, u, v, pr_blocks, tri)
    if cut_rows_fn is None:
        cut_rows_fn = lambda a, b, o: cut_rows(dev, a, b, o)  # noqa: E731
    if khop_step_fn is None:
        khop_step_fn = lambda r: khop_step_rows(dev, r)       # noqa: E731
    a0 = dev.node2block[u]
    khop, cut, cond = analytics_answers(
        dev.sizes, dev.deg, a0, kinds, v, cnt_a, cnt_b, ov,
        cut_rows_fn, khop_step_fn, khop_max)
    return jnp.select(
        [kinds == KIND_KHOP, kinds == KIND_CUT,
         kinds == KIND_CONDUCTANCE],
        [khop, cut, cond], base)


def _pagerank_while(dev: DeviceBlocks, damping: float, iters: int,
                    tol: float, row_sums_fn) -> jax.Array:
    """The shared power-iteration loop; ``row_sums_fn`` is the only part
    that differs between the local and routed engines."""
    vt = float(dev.num_nodes)
    p0 = jnp.full((dev.s,), 1.0 / vt, jnp.float64)

    def cond(carry):
        _, i, done = carry
        return (i < iters) & ~done

    def body(carry):
        p, i, _ = carry
        share = jax.lax.optimization_barrier(jnp.where(
            dev.deg > 0, p / jnp.maximum(dev.deg, 1e-300), 0.0))
        new, resid = pagerank_update(dev, p, row_sums_fn(share), damping)
        return new, i + 1, resid < tol

    p, _, _ = jax.lax.while_loop(
        cond, body, (p0, jnp.int32(0), jnp.bool_(False)))
    return p


class QueryEngine:
    """Single-device batched query engine over one summary.

    Shapes are static per engine (one compilation per summary + batch
    size, amortized over the serving lifetime). PageRank and triangle
    density are computed lazily on first use and then served as a gather /
    a broadcast scalar.
    """

    def __init__(self, summary: SummaryResult | BlockSummary, *,
                 damping: float = 0.85, pagerank_iters: int = 50,
                 pagerank_tol: float = 1e-10, triangle_chunk: int = 4096,
                 khop_max: int = 16):
        self.bs = (summary if isinstance(summary, BlockSummary)
                   else build_block_summary(summary))
        self.damping = damping
        self.pagerank_iters = pagerank_iters
        self.pagerank_tol = pagerank_tol
        self.triangle_chunk = triangle_chunk
        self.khop_max = khop_max
        self._pr_blocks = None
        self._tri = None
        with enable_x64():
            self.dev = device_blocks(self.bs)
            self._degree = jax.jit(degree_kernel)
            self._adjacency = jax.jit(adjacency_kernel)
            self._answer = jax.jit(answer_kernel)
            self._answer_full = jax.jit(
                lambda dev, kinds, u, v, pr, tri, ca, cb, ov:
                answer_kernel_full(dev, kinds, u, v, pr, tri, ca, cb, ov,
                                   khop_max))
            self._pagerank = jax.jit(
                lambda dev: _pagerank_while(
                    dev, damping, pagerank_iters, pagerank_tol,
                    lambda share: pagerank_row_sums(dev, share)))
            self._triangle = jax.jit(
                lambda dev: jnp.sum(triangle_rows(dev, triangle_chunk)))

    # ------------------------------------------------ lazy global queries
    def pagerank_blocks(self) -> jax.Array:
        if self._pr_blocks is None:
            with enable_x64():
                self._pr_blocks = self._pagerank(self.dev)
        return self._pr_blocks

    def triangle_density(self) -> float:
        if self._tri is None:
            with enable_x64():
                self._tri = self._triangle(self.dev)
        return float(self._tri)

    def pagerank_nodes(self, u) -> np.ndarray:
        pr = self.pagerank_blocks()
        with enable_x64():
            out = pr[self.dev.node2block[jnp.asarray(u, jnp.int32)]]
        return np.asarray(out)

    # --------------------------------------------------- batched queries
    def expected_degree(self, u) -> np.ndarray:
        with enable_x64():
            return np.asarray(
                self._degree(self.dev, jnp.asarray(u, jnp.int32)))

    def adjacency_weight(self, u, v) -> np.ndarray:
        with enable_x64():
            return np.asarray(self._adjacency(
                self.dev, jnp.asarray(u, jnp.int32),
                jnp.asarray(v, jnp.int32)))

    def answer_batch(self, kinds, u, v, cnt_a=None, cnt_b=None,
                     ov=None) -> np.ndarray:
        """Mixed-kind batch: ``kinds``/``u``/``v`` are int32[B]; returns
        float64[B]. The global-query inputs (PageRank vector, triangle
        scalar) are materialized only if the batch asks for them. Batches
        containing analytics kinds (khop/cut/conductance) go through the
        extended kernel; ``cnt_a``/``cnt_b``/``ov`` are the [B, S] count
        rows from :func:`pack_set_counts` (zeros when absent)."""
        kinds = np.asarray(kinds, np.int32)
        pr = (self.pagerank_blocks() if (kinds == KIND_PAGERANK).any()
              else None)
        tri = (self.triangle_density() if (kinds == KIND_TRIANGLE).any()
               else 0.0)
        needs = bool(np.isin(kinds, _ANALYTIC_KINDS).any())
        with enable_x64():
            if pr is None:
                pr = jnp.zeros((self.dev.s,), jnp.float64)
            args = (self.dev, jnp.asarray(kinds),
                    jnp.asarray(u, jnp.int32), jnp.asarray(v, jnp.int32),
                    pr, jnp.asarray(tri, jnp.float64))
            if not needs:
                return np.asarray(self._answer(*args))
            shape = (kinds.shape[0], self.dev.s)
            ca, cb, oo = (
                jnp.zeros(shape, jnp.float64) if x is None
                else jnp.asarray(x, jnp.float64)
                for x in (cnt_a, cnt_b, ov))
            return np.asarray(self._answer_full(*args, ca, cb, oo))

    # ------------------------------------------------- analytics queries
    def cut_weight(self, sets_a, sets_b) -> np.ndarray:
        """Batched Ĝ cut weight between node-set pairs (length-B lists)."""
        b = len(sets_a)
        kinds = np.full(b, KIND_CUT, np.int32)
        ca, cb, ov = pack_set_counts(self.bs, kinds, sets_a, sets_b)
        z = np.zeros(b, np.int32)
        return self.answer_batch(kinds, z, z, ca, cb, ov)

    def conductance(self, sets_a) -> np.ndarray:
        """Batched Ĝ conductance of node sets (length-B list)."""
        b = len(sets_a)
        kinds = np.full(b, KIND_CONDUCTANCE, np.int32)
        ca, cb, ov = pack_set_counts(self.bs, kinds, sets_a, None)
        z = np.zeros(b, np.int32)
        return self.answer_batch(kinds, z, z, ca, cb, ov)

    def k_hop_size(self, u, k) -> np.ndarray:
        """Batched expected k-hop neighborhood size (u, k broadcast)."""
        u = np.asarray(u, np.int32).ravel()
        k = np.broadcast_to(np.asarray(k, np.int32), u.shape)
        kinds = np.full(u.shape, KIND_KHOP, np.int32)
        return self.answer_batch(kinds, u, k)


class RoutedQueryEngine:
    """Owner-routed multi-device engine: same kernels, psum'd merge.

    Each supernode (block) is owned by ``MeshRules.owner(id, salt)`` — the
    re-drawable hash the distributed merge step already routes pairs with,
    so tooling that predicts record placement agrees across subsystems.
    Per-node queries are answered only by the owner of the target's block;
    global queries (PageRank rows, triangle rows) are computed per owned
    row and merged with a psum of disjoint contributions — exact, and
    bit-identical to :class:`QueryEngine` because every row reduces the
    same entries in the same order (tests/query_serve_check.py).

    A mesh change (elastic shrink/grow) is a routing-table rebuild:
    construct a new engine on the survivor mesh — the owner hash only
    depends on device *count* and salt.
    """

    def __init__(self, summary: SummaryResult | BlockSummary, mesh, *,
                 salt: int = 0, damping: float = 0.85,
                 pagerank_iters: int = 50, pagerank_tol: float = 1e-10,
                 triangle_chunk: int = 4096, khop_max: int = 16):
        self.bs = (summary if isinstance(summary, BlockSummary)
                   else build_block_summary(summary))
        self.mesh = mesh
        self.rules = make_rules(mesh, "summarize")
        self.salt = salt
        self.khop_max = khop_max
        self.axis_names = tuple(mesh.axis_names)
        self._pr_blocks = None
        self._tri = None
        axis_names = self.axis_names
        rep = self.rules.replicated

        with enable_x64():
            self.dev = device_blocks(self.bs)
            # routing table: block index -> owning device (host-built once;
            # rebuilt by constructing a new engine after a re-mesh)
            self.block_owner = jnp.asarray(np.asarray(self.rules.owner(
                jnp.asarray(self.bs.ids, jnp.int32),
                jnp.uint32(salt))), jnp.int32)

            def my_device():
                return jax.lax.axis_index(axis_names).astype(jnp.int32)

            def routed_rows(x_rows, owner):
                """Keep rows this device owns, psum the one-hot merge."""
                mine = owner == my_device()
                return jax.lax.psum(jnp.where(mine, x_rows, 0.0),
                                    axis_names)

            def pr_body(dev, owner):
                return _pagerank_while(
                    dev, damping, pagerank_iters, pagerank_tol,
                    lambda share: routed_rows(
                        pagerank_row_sums(dev, share), owner))

            self._pagerank = jax.jit(shard_map(
                pr_body, mesh=mesh, in_specs=(rep, rep), out_specs=rep,
                check_vma=False))

            def tri_body(dev, owner):
                tri = routed_rows(triangle_rows(dev, triangle_chunk),
                                  owner)
                return jnp.sum(tri)

            self._triangle = jax.jit(shard_map(
                tri_body, mesh=mesh, in_specs=(rep, rep), out_specs=rep,
                check_vma=False))

            def route_mask(dev, owner, kinds, u):
                """Which slots this device answers (disjoint across devs)."""
                is_global = jnp.zeros(kinds.shape, bool)
                for k in _GLOBAL_KINDS:
                    is_global |= kinds == k
                target = owner[dev.node2block[u]]
                return jnp.where(is_global, my_device() == 0,
                                 target == my_device())

            def answer_body(dev, owner, kinds, u, v, pr_blocks, tri):
                ans = answer_kernel(dev, kinds, u, v, pr_blocks, tri)
                mine = route_mask(dev, owner, kinds, u)
                return jax.lax.psum(jnp.where(mine, ans, 0.0), axis_names)

            self._answer = jax.jit(shard_map(
                answer_body, mesh=mesh, in_specs=(rep,) * 7,
                out_specs=rep, check_vma=False))

            def answer_full_body(dev, owner, kinds, u, v, pr_blocks, tri,
                                 ca, cb, ov):
                mine_rows = owner[None, :] == my_device()

                def cut_fn(a_, b_, o_):
                    rows = cut_rows(dev, a_, b_, o_)
                    return jax.lax.psum(jnp.where(mine_rows, rows, 0.0),
                                        axis_names)

                def step_fn(r):
                    stepped = jnp.where(mine_rows,
                                        khop_step_rows(dev, r), False)
                    return jax.lax.psum(stepped.astype(jnp.int32),
                                        axis_names) > 0

                ans = answer_kernel_full(dev, kinds, u, v, pr_blocks, tri,
                                         ca, cb, ov, khop_max,
                                         cut_fn, step_fn)
                mine = route_mask(dev, owner, kinds, u)
                return jax.lax.psum(jnp.where(mine, ans, 0.0), axis_names)

            self._answer_full = jax.jit(shard_map(
                answer_full_body, mesh=mesh, in_specs=(rep,) * 10,
                out_specs=rep, check_vma=False))

    def owner_counts(self) -> np.ndarray:
        """Blocks per owning device — the routing-table histogram."""
        return np.bincount(np.asarray(self.block_owner),
                           minlength=self.rules.n_devices)

    def pagerank_blocks(self) -> jax.Array:
        if self._pr_blocks is None:
            with enable_x64(), self.mesh:
                self._pr_blocks = self._pagerank(self.dev,
                                                 self.block_owner)
        return self._pr_blocks

    def pagerank_nodes(self, u) -> np.ndarray:
        pr = self.pagerank_blocks()
        with enable_x64():
            out = pr[self.dev.node2block[jnp.asarray(u, jnp.int32)]]
        return np.asarray(out)

    def triangle_density(self) -> float:
        if self._tri is None:
            with enable_x64(), self.mesh:
                self._tri = self._triangle(self.dev, self.block_owner)
        return float(self._tri)

    def answer_batch(self, kinds, u, v, cnt_a=None, cnt_b=None,
                     ov=None) -> np.ndarray:
        kinds = np.asarray(kinds, np.int32)
        pr = (self.pagerank_blocks() if (kinds == KIND_PAGERANK).any()
              else None)
        tri = (self.triangle_density() if (kinds == KIND_TRIANGLE).any()
               else 0.0)
        needs = bool(np.isin(kinds, _ANALYTIC_KINDS).any())
        with enable_x64(), self.mesh:
            if pr is None:
                pr = jnp.zeros((self.dev.s,), jnp.float64)
            args = (self.dev, self.block_owner, jnp.asarray(kinds),
                    jnp.asarray(u, jnp.int32), jnp.asarray(v, jnp.int32),
                    pr, jnp.asarray(tri, jnp.float64))
            if not needs:
                return np.asarray(self._answer(*args))
            shape = (kinds.shape[0], self.dev.s)
            ca, cb, oo = (
                jnp.zeros(shape, jnp.float64) if x is None
                else jnp.asarray(x, jnp.float64)
                for x in (cnt_a, cnt_b, ov))
            return np.asarray(self._answer_full(*args, ca, cb, oo))

    cut_weight = QueryEngine.cut_weight
    conductance = QueryEngine.conductance
    k_hop_size = QueryEngine.k_hop_size


# ------------------------------------------------------ partitioned tier
# DESIGN.md §16: each device keeps only the flat CSR entries of its owned
# rows plus precomputed halo tables; cross-block lookups are resolved by
# all-gathering the owned-value *slab* (size ~S/P per device) and indexing
# it with (src_device, src_position) halo coordinates — the full summary
# is never materialized on any device.

def stacked_entries(bs: BlockSummary, gids):
    """The CSR entries of each row set's rows (``gids`` [P, N], -1
    padding), row-major: ``(ent int32[P, E], indptr int32[P, N + 1])``,
    ``ent`` being entry indices into ``bs`` (-1 past a set's end) and
    ``indptr`` each row's bounds within its set."""
    gids = np.asarray(gids)
    lens = np.where(gids >= 0, np.diff(bs.indptr)[np.maximum(gids, 0)], 0)
    indptr = np.zeros((gids.shape[0], gids.shape[1] + 1), np.int64)
    np.cumsum(lens, axis=1, out=indptr[:, 1:])
    ent = np.full((gids.shape[0], max(1, int(indptr[:, -1].max()))), -1,
                  np.int32)
    for q in range(gids.shape[0]):
        row = np.repeat(np.arange(gids.shape[1]), lens[q])
        n = row.size
        ent[q, :n] = (bs.indptr[gids[q, row]]
                      + np.arange(n) - indptr[q, row])
    return ent, indptr.astype(np.int32)


@dataclasses.dataclass(frozen=True)
class PartitionTables:
    """Host-built partition + halo index tables for one (summary, P).

    Deterministic function of ``(BlockSummary, owner, n_devices,
    dense_row_nnz)`` — rebuilt from scratch on an elastic re-mesh; the
    halo-table property test pins determinism and coverage. All per-device
    lists are padded to the per-table max with -1.

    * ``own_gids[p]``      — global block ids device p owns (sorted);
    * ``own_ent[p]``       — their CSR entries, row-major (``own_indptr``
      bounds each owned row);
    * ``halo_*[p]``        — every remote block referenced by p's rows,
      with its (owner device, position-in-owner's-list) coordinates: the
      PageRank share exchange gathers owned slabs and reads these;
    * ``row_halo_gids[p]`` — the non-dense subset whose full rows are
      resident on p (triangle wedge closure needs whole rows);
    * ``dense_gids``       — rows with nnz > dense_row_nnz ("adversarially
      dense"): excluded from every resident halo and fetched at kernel
      time via a second-hop all-gather of the owner-held dense slab;
    * ``loc_share/loc_row[p, e]`` — per owned entry, the extended index of
      its column in [own | halo | (dense) | sentinel].
    """

    n_devices: int
    s: int
    d: int
    dense_row_nnz: int | None
    owner: np.ndarray          # int32[S] block -> device
    block_pos: np.ndarray      # int32[S] position in owner's own list
    own_gids: np.ndarray       # int32[P, S_own]
    own_ent: np.ndarray        # int32[P, E_own]
    own_indptr: np.ndarray     # int32[P, S_own + 1]
    halo_gids: np.ndarray      # int32[P, H]
    halo_src_dev: np.ndarray   # int32[P, H]
    halo_src_pos: np.ndarray   # int32[P, H]
    row_halo_gids: np.ndarray  # int32[P, Ht]
    dense_gids: np.ndarray     # int32[n_dense] (sorted)
    dense_slots: np.ndarray    # int32[P, Dm] dense rows per owner
    loc_share: np.ndarray      # int32[P, E_own]
    loc_row: np.ndarray        # int32[P, E_own]


def build_partition_tables(bs: BlockSummary, owner, n_devices: int,
                           dense_row_nnz: int | None = None,
                           ) -> PartitionTables:
    """Build the per-device row partition and halo index tables (host)."""
    owner = np.asarray(owner, np.int32)
    p = int(n_devices)
    s = bs.num_blocks
    d = max(1, bs.max_row_nnz())

    row_nnz = np.diff(bs.indptr)
    dense = np.zeros(s, bool)
    if dense_row_nnz is not None and s:
        dense = row_nnz > int(dense_row_nnz)
    dense_gids = np.flatnonzero(dense).astype(np.int32)

    own_lists = [np.flatnonzero(owner == q).astype(np.int32)
                 for q in range(p)]
    s_own = max([1] + [l.size for l in own_lists])
    block_pos = np.zeros(s, np.int32)
    for l in own_lists:
        block_pos[l] = np.arange(l.size, dtype=np.int32)
    own_gids = np.full((p, s_own), -1, np.int32)
    for q, l in enumerate(own_lists):
        own_gids[q, :l.size] = l
    own_ent, own_indptr = stacked_entries(bs, own_gids)
    own_cols = [bs.cols[own_ent[q, :own_indptr[q, -1]]] for q in range(p)]

    dense_lists = [l[dense[l]] for l in own_lists]
    dmax = max([1] + [l.size for l in dense_lists])
    dense_slots = np.full((p, dmax), -1, np.int32)
    dense_slab_pos = np.full(s, -1, np.int32)  # gid -> slot in [P·Dm] slab
    for q, l in enumerate(dense_lists):
        dense_slots[q, :l.size] = l
        dense_slab_pos[l] = q * dmax + np.arange(l.size, dtype=np.int32)

    halo_lists, row_halo_lists = [], []
    for q in range(p):
        refs = np.unique(own_cols[q]).astype(np.int32)
        remote = refs[owner[refs] != q]
        halo_lists.append(remote)
        row_halo_lists.append(remote[~dense[remote]])
    h = max([1] + [l.size for l in halo_lists])
    ht = max([1] + [l.size for l in row_halo_lists])

    halo_gids = np.full((p, h), -1, np.int32)
    halo_src_dev = np.zeros((p, h), np.int32)
    halo_src_pos = np.zeros((p, h), np.int32)
    row_halo_gids = np.full((p, ht), -1, np.int32)
    share_sent = s_own + h
    row_sent = s_own + ht + p * dmax
    loc_share = np.full(own_ent.shape, share_sent, np.int32)
    loc_row = np.full(own_ent.shape, row_sent, np.int32)
    for q in range(p):
        own, hl, rhl = own_lists[q], halo_lists[q], row_halo_lists[q]
        halo_gids[q, :hl.size] = hl
        halo_src_dev[q, :hl.size] = owner[hl]
        halo_src_pos[q, :hl.size] = block_pos[hl]
        row_halo_gids[q, :rhl.size] = rhl
        # gid -> extended-index maps for this device
        share_map = np.full(s, share_sent, np.int64)
        share_map[hl] = s_own + np.arange(hl.size)
        share_map[own] = block_pos[own]
        row_map = np.full(s, row_sent, np.int64)
        dm = np.flatnonzero(dense_slab_pos >= 0)
        row_map[dm] = s_own + ht + dense_slab_pos[dm]
        row_map[rhl] = s_own + np.arange(rhl.size)
        row_map[own] = block_pos[own]  # own rows win over the dense slab
        loc_share[q, :own_cols[q].size] = share_map[own_cols[q]]
        loc_row[q, :own_cols[q].size] = row_map[own_cols[q]]

    return PartitionTables(
        n_devices=p, s=s, d=d, dense_row_nnz=dense_row_nnz, owner=owner,
        block_pos=block_pos, own_gids=own_gids, own_ent=own_ent,
        own_indptr=own_indptr, halo_gids=halo_gids,
        halo_src_dev=halo_src_dev, halo_src_pos=halo_src_pos,
        row_halo_gids=row_halo_gids, dense_gids=dense_gids,
        dense_slots=dense_slots, loc_share=loc_share, loc_row=loc_row)


@dataclasses.dataclass(frozen=True)
class PartBlocks:
    """Device-sharded [P, ...] leaves of the partitioned tier (axis 0 is
    the device axis; each device addresses only its own [1, ...] slice
    inside shard_map). Rows are flat entries bounded by an indptr, as in
    :class:`DeviceBlocks`."""

    own_gids: jax.Array     # int32[P, S_own]
    own_indptr: jax.Array   # int32[P, S_own + 1]
    ent_row: jax.Array      # int32[P, E_own] owned entry's row (S_own: pad)
    cols: jax.Array         # int32[P, E_own] (-1: pad)
    sigma: jax.Array        # float64[P, E_own]
    degw: jax.Array         # float64[P, E_own]
    loc_share: jax.Array    # int32[P, E_own]
    loc_row: jax.Array      # int32[P, E_own]
    plan: tuple             # row_sum_plans of the owned rows
    halo_src_dev: jax.Array  # int32[P, H]
    halo_src_pos: jax.Array  # int32[P, H]
    rh_indptr: jax.Array    # int32[P, Ht + 1] resident halo rows
    rh_cols: jax.Array      # int32[P, E_ht]
    rh_sigma: jax.Array     # float64[P, E_ht]
    dn_indptr: jax.Array    # int32[P, Dm + 1] dense (second-hop) rows
    dn_cols: jax.Array      # int32[P, E_dm]
    dn_sigma: jax.Array     # float64[P, E_dm]


jax.tree_util.register_pytree_node(
    PartBlocks,
    lambda b: (tuple(getattr(b, f.name)
                     for f in dataclasses.fields(PartBlocks)), None),
    lambda _, leaves: PartBlocks(*leaves),
)


@dataclasses.dataclass(frozen=True)
class RepBlocks:
    """Replicated O(S)/O(V) metadata of the partitioned tier (only the
    row payload grows with the superedge count, so only it is worth
    partitioning)."""

    node2block: jax.Array  # int32[V]
    sizes: jax.Array       # float64[S]
    deg: jax.Array         # float64[S]
    owner: jax.Array       # int32[S]
    block_pos: jax.Array   # int32[S]
    gids_all: jax.Array    # int32[P, S_own] (replicated copy of own_gids)


jax.tree_util.register_pytree_node(
    RepBlocks,
    lambda b: (tuple(getattr(b, f.name)
                     for f in dataclasses.fields(RepBlocks)), None),
    lambda _, leaves: RepBlocks(*leaves),
)


def _squeeze_part(pb: PartBlocks) -> PartBlocks:
    """Drop the leading per-device axis inside shard_map bodies."""
    return jax.tree_util.tree_map(lambda x: x[0], pb)


class PartitionedQueryEngine:
    """Memory-partitioned routed engine: device-sharded block CSR rows.

    Same wire format and bit-identical answers as the replicated tiers,
    but each device's resident summary is its owned rows' entries (~nnz/P)
    plus the halo — the rows its owned rows reference on other devices —
    rather than the full CSR. Cross-device σ/share lookups go through the
    precomputed halo tables: PageRank all-gathers the owned [P, S_own]
    value slab per step and reads remote shares at (src_device,
    src_position); the triangle wedge closure keeps resident copies of
    (non-dense) halo rows. Rows denser than ``dense_row_nnz`` are excluded
    from every resident halo and fetched by a second-hop all-gather of the
    owner-held dense slab at kernel time, bounding resident memory against
    adversarially dense rows.

    Bit-identity holds for the same reason as the routed tier: every
    per-row reduction adds the same entries in the same CSR order (owned
    entries are listed row-major and summed along the same
    :func:`row_sum_plans` structure), per-row results are merged into
    canonical [S]-indexed vectors by a psum of disjoint scatters, and all
    post-row math is replicated. An elastic re-mesh is a table rebuild:
    construct a new engine on the survivor mesh.
    """

    def __init__(self, summary: SummaryResult | BlockSummary, mesh, *,
                 salt: int = 0, damping: float = 0.85,
                 pagerank_iters: int = 50, pagerank_tol: float = 1e-10,
                 triangle_chunk: int = 4096, khop_max: int = 16,
                 dense_row_nnz: int | None = None):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from repro.dist import owner_hash_np

        self.bs = (summary if isinstance(summary, BlockSummary)
                   else build_block_summary(summary))
        self.mesh = mesh
        self.rules = make_rules(mesh, "summarize")
        self.salt = salt
        self.khop_max = khop_max
        self.dense_row_nnz = dense_row_nnz
        self.axis_names = tuple(mesh.axis_names)
        self._pr_blocks = None
        self._tri = None
        axis_names = self.axis_names
        bs = self.bs
        n_dev = self.rules.n_devices
        owner = owner_hash_np(bs.ids, salt, n_dev)
        self.tables = t = build_partition_tables(
            bs, owner, n_dev, dense_row_nnz)
        s, d = t.s, t.d
        num_nodes = bs.num_nodes
        s_own = t.own_gids.shape[1]
        e_own = t.own_ent.shape[1]
        rh_ent, rh_indptr = stacked_entries(bs, t.row_halo_gids)
        dn_ent, dn_indptr = stacked_entries(bs, t.dense_slots)
        own_len = np.diff(t.own_indptr, axis=1)
        ent_row = np.full((n_dev, e_own), s_own, np.int32)
        for q in range(n_dev):
            row = np.repeat(np.arange(s_own, dtype=np.int32), own_len[q])
            ent_row[q, :row.size] = row

        def entries(ent, arr, fill):
            """Entry values of ``arr`` at ``ent`` ([P, E]), ``fill`` at -1."""
            out = arr[np.maximum(ent, 0)]
            out[ent < 0] = fill
            return out

        cols, sigma = bs.cols.astype(np.int32), bs.sigma.astype(np.float64)
        host_part = PartBlocks(
            own_gids=t.own_gids, own_indptr=t.own_indptr, ent_row=ent_row,
            cols=entries(t.own_ent, cols, -1),
            sigma=entries(t.own_ent, sigma, 0.0),
            degw=entries(t.own_ent, bs.deg_w.astype(np.float64), 0.0),
            loc_share=t.loc_share, loc_row=t.loc_row,
            plan=row_sum_plans(own_len),
            halo_src_dev=t.halo_src_dev, halo_src_pos=t.halo_src_pos,
            rh_indptr=rh_indptr, rh_cols=entries(rh_ent, cols, -1),
            rh_sigma=entries(rh_ent, sigma, 0.0),
            dn_indptr=dn_indptr, dn_cols=entries(dn_ent, cols, -1),
            dn_sigma=entries(dn_ent, sigma, 0.0))

        with enable_x64():
            shard = NamedSharding(mesh, P(axis_names))
            rep_sh = NamedSharding(mesh, P())

            def put(x, sh):
                return jax.device_put(jnp.asarray(x), sh)

            self.part = jax.tree_util.tree_map(lambda x: put(x, shard),
                                               host_part)
            self.rep = RepBlocks(
                node2block=put(bs.node2block.astype(np.int32), rep_sh),
                sizes=put(bs.sizes.astype(np.float64), rep_sh),
                deg=put(bs.deg.astype(np.float64), rep_sh),
                owner=put(t.owner, rep_sh),
                block_pos=put(t.block_pos, rep_sh),
                gids_all=put(t.own_gids, rep_sh),
            )
            part_spec = P(axis_names)
            rep_spec = P()

            def my_device():
                return jax.lax.axis_index(axis_names).astype(jnp.int32)

            def scatter1(vals, gids):
                """[S_own] owned values -> [S] canonical (pre-psum)."""
                safe = jnp.where(gids >= 0, gids, s)
                return jnp.zeros(s + 1, vals.dtype).at[safe].set(vals)[:s]

            def scatter2(vals, gids):
                """[B, S_own] -> [B, S] canonical (pre-psum)."""
                safe = jnp.where(gids >= 0, gids, s)
                out = jnp.zeros(vals.shape[:-1] + (s + 1,), vals.dtype)
                return out.at[:, safe].set(vals)[:, :s]

            def full_from_slab(slab, gids_all):
                """All-gathered owned slab [P, S_own] -> canonical [S]."""
                safe = jnp.where(gids_all >= 0, gids_all, s)
                return (jnp.zeros(s + 1, slab.dtype)
                        .at[safe.ravel()].set(slab.ravel())[:s])

            def owned_rows(pb, r):
                """Owned rows ``r`` as ``[R, D]`` views (cols, sigma)."""
                return row_views(pb.own_indptr[:-1], pb.own_indptr[1:],
                                 pb.cols, pb.sigma, r, d)

            # ------------------------------------------------- pagerank
            def pr_body(pb, rb):
                pb = _squeeze_part(pb)
                valid = pb.own_gids >= 0
                gsafe = jnp.where(valid, pb.own_gids, 0)
                deg_own = jnp.where(valid, rb.deg[gsafe], 0.0)
                vt = float(num_nodes)
                p0 = jnp.where(valid, 1.0 / vt, 0.0)

                def cond(carry):
                    _, i, done = carry
                    return (i < pagerank_iters) & ~done

                def body(carry):
                    p_own, i, _ = carry
                    share_own = jax.lax.optimization_barrier(jnp.where(
                        deg_own > 0,
                        p_own / jnp.maximum(deg_own, 1e-300), 0.0))
                    slab = jax.lax.all_gather(
                        jnp.stack([p_own, share_own]), axis_names)
                    halo_share = slab[pb.halo_src_dev, 1, pb.halo_src_pos]
                    share_ext = jnp.concatenate(
                        [share_own, halo_share,
                         jnp.zeros((1,), jnp.float64)])
                    rsum = planned_row_sums(
                        pb.plan, pb.degw * share_ext[pb.loc_share])
                    p_full = full_from_slab(slab[:, 0, :], rb.gids_all)
                    new = damped(p_full, rsum, rb.deg, rb.sizes, damping, vt)
                    new = jnp.where(valid, new, 0.0)
                    # max of the per-device maxima (the TPU all-reduces
                    # float64 only by sum)
                    resid = jnp.max(jax.lax.all_gather(
                        jnp.max(jnp.abs(new - p_own)), axis_names))
                    return new, i + 1, resid < pagerank_tol

                p_own, _, _ = jax.lax.while_loop(
                    cond, body,
                    (p0, jnp.int32(0), jnp.bool_(False)))
                slab = jax.lax.all_gather(p_own, axis_names)
                return full_from_slab(slab, rb.gids_all)

            self._pagerank = jax.jit(shard_map(
                pr_body, mesh=mesh, in_specs=(part_spec, rep_spec),
                out_specs=rep_spec, check_vma=False))

            # ------------------------------------------------- triangle
            def ext_rows(pb):
                """The wedge closure's rows [own | resident halo | gathered
                dense slab | empty sentinel] as flat entries with each
                row's ``(start, end)``."""
                n_own, n_rh = pb.cols.shape[0], pb.rh_cols.shape[0]
                n_dn = pb.dn_cols.shape[0]
                dn_ptr = (jax.lax.all_gather(pb.dn_indptr, axis_names)
                          + n_own + n_rh
                          + n_dn * jnp.arange(n_dev, dtype=jnp.int32)[:, None])
                zero = jnp.zeros((1,), jnp.int32)
                start = jnp.concatenate(
                    [pb.own_indptr[:-1], n_own + pb.rh_indptr[:-1],
                     dn_ptr[:, :-1].reshape(-1), zero])
                end = jnp.concatenate(
                    [pb.own_indptr[1:], n_own + pb.rh_indptr[1:],
                     dn_ptr[:, 1:].reshape(-1), zero])
                cols = jnp.concatenate(
                    [pb.cols, pb.rh_cols,
                     jax.lax.all_gather(pb.dn_cols, axis_names).reshape(-1)])
                sigma = jnp.concatenate(
                    [pb.sigma, pb.rh_sigma,
                     jax.lax.all_gather(pb.dn_sigma, axis_names).reshape(-1)])
                return start, end, cols, sigma

            def tri_body(pb, rb):
                pb = _squeeze_part(pb)
                start, end, ext_cols, ext_sigma = ext_rows(pb)
                row_i = jnp.minimum(pb.ent_row, s_own - 1)
                a_e = pb.own_gids[row_i]
                chunk = max(1, min(triangle_chunk, e_own))
                n_chunks = -(-e_own // chunk)
                ids = jnp.arange(n_chunks * chunk, dtype=jnp.int32)

                def one_chunk(idx):
                    e = jnp.minimum(idx, e_own - 1)
                    a = jnp.clip(a_e[e], 0, s - 1)
                    row_a, sig_a = owned_rows(pb, row_i[e])

                    def sigma_ca(c):
                        # σ_ca looked up in row a's own columns — the same
                        # float as the replicated global-key search since
                        # the CSR is symmetric (σ_ca == σ_ac)
                        srow = jnp.where(row_a < 0, s, row_a)  # ascending
                        pos_c = jnp.clip(
                            jax.vmap(jnp.searchsorted)(srow, c), 0, d - 1)
                        hit = jnp.take_along_axis(srow, pos_c, 1) == c
                        return jnp.where(hit, jnp.take_along_axis(
                            sig_a, pos_c, 1), 0.0)

                    cols_b, sig_b = row_views(start, end, ext_cols,
                                              ext_sigma, pb.loc_row[e], d)
                    return wedge_inner(a, pb.cols[e], cols_b, sig_b,
                                       rb.sizes, sigma_ca)

                inner = jax.lax.map(one_chunk, ids.reshape(n_chunks, chunk))
                w = triangle_weights(a_e, pb.cols, pb.sigma,
                                     inner.reshape(-1)[:e_own], rb.sizes)
                tri_full = jax.lax.psum(
                    scatter1(planned_row_sums(pb.plan, w), pb.own_gids),
                    axis_names)
                return jnp.sum(tri_full)

            self._triangle = jax.jit(shard_map(
                tri_body, mesh=mesh, in_specs=(part_spec, rep_spec),
                out_specs=rep_spec, check_vma=False))

            # --------------------------------------------------- answers
            def base_answers(pb, rb, kinds, u, v, pr_full, tri):
                """Point/global answers from owned rows only (valid on the
                routing owner; garbage elsewhere is masked by routing)."""
                a0 = rb.node2block[u]
                bblk = rb.node2block[v]
                i = jnp.clip(rb.block_pos[a0], 0, s_own - 1)
                row, sig_row = owned_rows(pb, i)             # [B, D]
                srow = jnp.where(row < 0, s, row)
                pos = jax.vmap(jnp.searchsorted)(srow, bblk[:, None])
                pos = jnp.clip(pos[:, 0], 0, d - 1)
                hit = jnp.take_along_axis(
                    srow, pos[:, None], 1)[:, 0] == bblk
                sig = jnp.where(
                    hit,
                    jnp.take_along_axis(sig_row, pos[:, None], 1)[:, 0], 0.0)
                adj = jnp.where(u == v, 0.0, sig)
                return jnp.select(
                    [kinds == KIND_DEGREE, kinds == KIND_ADJACENCY,
                     kinds == KIND_PAGERANK, kinds == KIND_TRIANGLE],
                    [rb.deg[a0], adj, pr_full[a0],
                     jnp.broadcast_to(tri, kinds.shape)], 0.0)

            def route_mask(rb, kinds, u):
                is_global = jnp.zeros(kinds.shape, bool)
                for k in _GLOBAL_KINDS:
                    is_global |= kinds == k
                target = rb.owner[rb.node2block[u]]
                return jnp.where(is_global, my_device() == 0,
                                 target == my_device())

            def answer_body(pb, rb, kinds, u, v, pr_full, tri):
                pb = _squeeze_part(pb)
                ans = base_answers(pb, rb, kinds, u, v, pr_full, tri)
                mine = route_mask(rb, kinds, u)
                return jax.lax.psum(jnp.where(mine, ans, 0.0), axis_names)

            self._answer = jax.jit(shard_map(
                answer_body, mesh=mesh,
                in_specs=(part_spec,) + (rep_spec,) * 6,
                out_specs=rep_spec, check_vma=False))

            def answer_full_body(pb, rb, kinds, u, v, pr_full, tri,
                                 ca, cb, ov):
                pb = _squeeze_part(pb)
                base = base_answers(pb, rb, kinds, u, v, pr_full, tri)
                gsafe = jnp.clip(pb.own_gids, 0, s - 1)
                valid = pb.own_gids >= 0
                col_e = jnp.clip(pb.cols, 0, s - 1)
                row_e = jnp.minimum(pb.ent_row, s_own - 1)
                sdiag = planned_row_sums(
                    pb.plan,
                    jnp.where(col_e == gsafe[row_e], pb.sigma, 0.0))

                def cut_fn(a_, b_, o_):
                    rowsum = planned_row_sums(
                        pb.plan, pb.sigma[:, None] * b_.T[col_e])
                    rows_own = jnp.where(
                        valid[None, :],
                        a_[:, gsafe] * rowsum.T - sdiag[None, :] * o_[:, gsafe],
                        0.0)
                    return jax.lax.psum(
                        scatter2(rows_own, pb.own_gids), axis_names)

                def step_fn(r):
                    hit = r[:, col_e] & (pb.sigma > 0)[None, :]  # [B, E_own]
                    per_row = planned_row_sums(pb.plan,
                                               hit.T.astype(jnp.int32))
                    full = jax.lax.psum(
                        scatter2(per_row.T, pb.own_gids), axis_names)
                    return full > 0

                a0 = rb.node2block[u]
                khop, cut, cond = analytics_answers(
                    rb.sizes, rb.deg, a0, kinds, v, ca, cb, ov,
                    cut_fn, step_fn, khop_max)
                ans = jnp.select(
                    [kinds == KIND_KHOP, kinds == KIND_CUT,
                     kinds == KIND_CONDUCTANCE],
                    [khop, cut, cond], base)
                mine = route_mask(rb, kinds, u)
                return jax.lax.psum(jnp.where(mine, ans, 0.0), axis_names)

            self._answer_full = jax.jit(shard_map(
                answer_full_body, mesh=mesh,
                in_specs=(part_spec,) + (rep_spec,) * 9,
                out_specs=rep_spec, check_vma=False))

    # ------------------------------------------------------------ queries
    def owner_counts(self) -> np.ndarray:
        return np.bincount(self.tables.owner,
                           minlength=self.rules.n_devices)

    def pagerank_blocks(self) -> jax.Array:
        if self._pr_blocks is None:
            with enable_x64(), self.mesh:
                self._pr_blocks = self._pagerank(self.part, self.rep)
        return self._pr_blocks

    def pagerank_nodes(self, u) -> np.ndarray:
        pr = self.pagerank_blocks()
        with enable_x64():
            out = pr[self.rep.node2block[jnp.asarray(u, jnp.int32)]]
        return np.asarray(out)

    def triangle_density(self) -> float:
        if self._tri is None:
            with enable_x64(), self.mesh:
                self._tri = self._triangle(self.part, self.rep)
        return float(self._tri)

    def answer_batch(self, kinds, u, v, cnt_a=None, cnt_b=None,
                     ov=None) -> np.ndarray:
        kinds = np.asarray(kinds, np.int32)
        pr = (self.pagerank_blocks() if (kinds == KIND_PAGERANK).any()
              else None)
        tri = (self.triangle_density() if (kinds == KIND_TRIANGLE).any()
               else 0.0)
        needs = bool(np.isin(kinds, _ANALYTIC_KINDS).any())
        s = self.tables.s
        with enable_x64(), self.mesh:
            if pr is None:  # placed as the PageRank vector is: one program
                pr = jax.device_put(jnp.zeros((s,), jnp.float64),
                                    self.rep.sizes.sharding)
            args = (self.part, self.rep, jnp.asarray(kinds),
                    jnp.asarray(u, jnp.int32), jnp.asarray(v, jnp.int32),
                    pr, jnp.asarray(tri, jnp.float64))
            if not needs:
                return np.asarray(self._answer(*args))
            shape = (kinds.shape[0], s)
            ca, cb, oo = (
                jnp.zeros(shape, jnp.float64) if x is None
                else jnp.asarray(x, jnp.float64)
                for x in (cnt_a, cnt_b, ov))
            return np.asarray(self._answer_full(*args, ca, cb, oo))

    cut_weight = QueryEngine.cut_weight
    conductance = QueryEngine.conductance
    k_hop_size = QueryEngine.k_hop_size

    # ------------------------------------------------- memory accounting
    def partition_stats(self) -> dict:
        t = self.tables
        return {
            "devices": int(t.n_devices),
            "s": int(t.s),
            "d": int(t.d),
            "s_own_max": int(t.own_gids.shape[1]),
            "entries_own_max": int(t.own_ent.shape[1]),
            "halo_max": int(t.halo_gids.shape[1]),
            "row_halo_max": int(t.row_halo_gids.shape[1]),
            "dense_rows": int(t.dense_gids.size),
            "owner_counts": self.owner_counts().tolist(),
            "halo_counts": (t.halo_gids >= 0).sum(axis=1).tolist(),
            "resident_bytes_per_device": self.resident_bytes_per_device(),
            "replicated_row_bytes": self.replicated_row_bytes(),
        }

    def resident_bytes_per_device(self) -> int:
        """Measured per-device bytes of the sharded row payload (every
        [P, ...] leaf shards evenly: one [1, ...] slice per device)."""
        return int(sum(
            leaf.addressable_shards[0].data.nbytes
            for leaf in jax.tree_util.tree_leaves(self.part)))

    def replicated_row_bytes(self) -> int:
        """What each device of a replicated tier keeps for the same rows:
        the bytes of :class:`DeviceBlocks`' row leaves (``ROW_LEAVES``)."""
        host = host_blocks(self.bs)
        return int(sum(x.nbytes for name in ROW_LEAVES
                       for x in jax.tree_util.tree_leaves(host[name])))
