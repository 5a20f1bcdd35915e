"""Distributed SSumM: edge-sharded summarization under shard_map.

Scale story (the paper's headline): one 64 GB host caps the reference
implementation at ~0.8 B edges; here edges are sharded over every mesh axis
while the partition vector (``node2super``/``size``, 4 B/node) is replicated
— web-uk-05 (39.5 M nodes, 0.78 B edges) takes ~12 MB of edges + ~316 MB of
replicated state per chip on a 256-chip pod (dry-run proof in EXPERIMENTS.md
§Dry-run).

Scheme (DESIGN.md §7):
  * **ownership**: two interchangeable groupings of one backend
    (:func:`make_distributed_backend`):

      - ``grouping="hash"`` — supernode ``A`` is owned by device
        ``hash_t(A) mod n_dev``; the hash is re-drawn every iteration so all
        supernode pairs are eventually co-owned (candidate sets never span
        owners — the exact analogue of the paper's disjoint candidate sets);
      - ``grouping="compact"`` — candidate groups are computed identically on
        every device (shingle pmin + replicated chunking) and device ``d``
        owns groups ``g ≡ d (mod n_dev)``, with compact ``[G_own·C, D]``
        neighbor tables (~40 MB at web-uk scale, where the hash path's
        ``[V, D]`` tables would be ~20 GB/device);

  * **pair exchange**: each device aggregates its local edge shard into
    partial (lo, hi, cnt) pair records and routes each record to *both*
    endpoint owners with a fixed-capacity ``all_to_all`` bucket shuffle;
    owners re-aggregate to exact global pair counts;
  * **merge round**: owners build group tables and run the merge-gain kernel
    locally (dispatched through the :mod:`repro.kernels.ops` registry);
    accepted (a, b) merge lists are ``all_gather``-ed and applied
    identically to the replicated partition on every device;
  * **metrics**: per-pair closed forms are summed over *lo-owned* pairs only
    (each pair counted once), ``psum``-ed, with ω_max ``pmax``-ed first so
    Size(Ḡ) is bit-identical to the single-device evaluation.

Bucket overflow (records beyond capacity) is counted and reported in the
stats — with the default capacity factor the shuffle is exact; tests verify
equality with the single-device pair table on multihost CPU meshes.

The final drop-to-k-bits phase (Sect. 3.2.4) is edge-sharded too (the
backend's ``sparsify``, DESIGN.md §7): pairs are exchanged to their *lo*
owner only (each pair counted exactly once), the ξ-th smallest ΔRE is found
by the psum'd histogram selection of
:func:`repro.core.sparsify.radix_select_kth` instead of a replicated sort,
and the resulting drop mask stays sharded — the whole pipeline
(merge → sparsify → metrics) runs without gathering edges to one host.

The iteration *driver* is the engine's (DESIGN.md §12):
:class:`DistributedBackend` plugs into
:class:`repro.core.engine.SummaryEngine`, and its ``run_chunk`` runs up to
``cfg.driver_chunk`` merge rounds per dispatch inside a ``lax.while_loop``
*within* the shard_map body — scalar metrics cross to the host only on
chunk boundaries instead of a full device→host sync every round.

Edge shards themselves arrive through :mod:`repro.graphs.feed`
(DESIGN.md §11): real graphs are sliced straight out of the mmap'd binary
CSR cache into per-device shards (host staging = one shard, never a
full-|E| array), so the backend receives inputs already committed to
``MeshRules.edge_spec`` and nothing upstream densifies the edge list.

``make_distributed_step`` / ``make_distributed_step_compact`` /
``make_distributed_sparsify`` remain as thin compat shims over the one
backend builder.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from repro.core import costs, shingles, sparsify, tables
from repro.core.merge import apply_merges, select_matching
from repro.core.types import PairTable, SummaryConfig, SummaryState, init_state
from repro.dist import make_rules
from repro.kernels import ops as kops
from repro.utils import boundaries_from_keys, segment_ids_from_boundaries

# Per-round scalar stats of the distributed merge step (fixed key set →
# fixed-shape on-device chunk buffers; see engine.Backend).
DIST_STAT_KEYS = (
    "size_bits",
    "re1",
    "nmerges",
    "num_supernodes",
    "num_superedges",
    "overflow",
)


def _ordered_psum(x, axis_names):
    """Order-invariant float sum across the mesh: all_gather the per-device
    partials, then reduce them locally in device order. A raw ``psum``'s
    partial-sum grouping depends on the process topology (gloo's
    cross-process ring groups differently from the single-process
    reduction, ~1 ulp on f32 accumulations), which would break the
    launcher-JSON bit-identity contract between single- and multi-process
    runs of the same global mesh (DESIGN.md §15). Integer-valued psums
    (counts, histograms) are exact in any order and stay plain ``psum``."""
    return jnp.sum(jax.lax.all_gather(x, axis_names), axis=0)


def _local_pairs(src, dst, node2super, num_nodes: int):
    """Local partial pair table from this device's edge shard (sorted)."""
    e = src.shape[0]
    pad = src < 0  # padded edge slots
    su = jnp.where(pad, num_nodes, node2super[jnp.maximum(src, 0)])
    sv = jnp.where(pad, num_nodes, node2super[jnp.maximum(dst, 0)])
    lo = jnp.minimum(su, sv)
    hi = jnp.maximum(su, sv)
    lo_s, hi_s = jax.lax.sort((lo, hi), num_keys=2)
    is_new = boundaries_from_keys(lo_s, hi_s)
    pid = segment_ids_from_boundaries(is_new)
    cnt = jax.ops.segment_sum(
        jnp.where(lo_s < num_nodes, 1.0, 0.0), pid, num_segments=e
    )
    plo = jnp.zeros((e,), jnp.int32).at[pid].max(lo_s)
    phi = jnp.zeros((e,), jnp.int32).at[pid].max(hi_s)
    valid = (jnp.arange(e) <= pid[-1]) & (plo < num_nodes) & (cnt > 0)
    return plo, phi, jnp.where(valid, cnt, 0.0), valid


def _route(plo, phi, cnt, valid, owner, n_dev: int, cap: int):
    """Pack pair records into per-destination buckets [n_dev, cap, 3]."""
    n = plo.shape[0]
    dest = jnp.where(valid, owner, n_dev)
    order = jnp.argsort(dest)
    dest_s = dest[order]
    is_new = boundaries_from_keys(dest_s)
    idx = jnp.arange(n, dtype=jnp.int32)
    start = jax.lax.cummax(jnp.where(is_new, idx, 0))
    slot = idx - start
    ok = (slot < cap) & (dest_s < n_dev)
    flat = jnp.where(ok, dest_s * cap + slot, n_dev * cap)
    rec = jnp.stack(
        [plo[order].astype(jnp.float32), phi[order].astype(jnp.float32), cnt[order]],
        axis=-1,
    )
    buck = jnp.full((n_dev * cap + 1, 3), -1.0, jnp.float32)
    buck = buck.at[flat].set(rec, mode="drop")[:-1]
    overflow = jnp.sum(((~ok) & (dest_s < n_dev)).astype(jnp.int32))
    return buck.reshape(n_dev, cap, 3), overflow


def _aggregate(recv, num_nodes: int):
    """Merge partial pair records from all sources into exact global counts."""
    rlo = recv[:, 0].astype(jnp.int32)
    rhi = recv[:, 1].astype(jnp.int32)
    rvalid = recv[:, 0] >= 0
    key_lo = jnp.where(rvalid, rlo, num_nodes)
    key_hi = jnp.where(rvalid, rhi, num_nodes)
    rcnt = jnp.where(rvalid, recv[:, 2], 0.0)
    klo, khi, kcnt = jax.lax.sort((key_lo, key_hi, rcnt), num_keys=2)
    is_new = boundaries_from_keys(klo, khi)
    pid = segment_ids_from_boundaries(is_new)
    m = klo.shape[0]
    gcnt = jax.ops.segment_sum(kcnt, pid, num_segments=m)
    glo = jnp.zeros((m,), jnp.int32).at[pid].max(klo)
    ghi = jnp.zeros((m,), jnp.int32).at[pid].max(khi)
    gvalid = (jnp.arange(m) <= pid[-1]) & (glo < num_nodes) & (gcnt > 0)
    return glo, ghi, jnp.where(gvalid, gcnt, 0.0), gvalid


def _exchange(plo, phi, cnt, valid, own_lo, own_hi, axis_names, n_dev, cap,
              num_nodes):
    """Route partial pair records to their owner(s) and re-aggregate.

    ``own_hi=None`` routes each pair to its *lo* owner only (the sparsify
    phase — each pair counted exactly once); otherwise records go to both
    endpoint owners (the merge phase — owners need their full adjacency).
    """
    b1, of1 = _route(plo, phi, cnt, valid, own_lo, n_dev, cap)
    if own_hi is None:
        buck, overflow = b1, of1
    else:
        b2, of2 = _route(plo, phi, cnt, valid & (own_hi != own_lo), own_hi,
                         n_dev, cap)
        buck = jnp.concatenate([b1, b2], axis=1)  # [n_dev, 2cap, 3]
        overflow = of1 + of2
    recv = jax.lax.all_to_all(
        buck, axis_names, split_axis=0, concat_axis=0, tiled=True
    )
    glo, ghi, gcnt, gvalid = _aggregate(recv.reshape(-1, 3), num_nodes)
    return glo, ghi, gcnt, gvalid, overflow


def _round_metrics(cfg, state, glo, ghi, gcnt, mine, cbar, log2v, v,
                   axis_names, s_count, nmerges_g, overflow):
    """Exact global Eq. (4)/(2) metrics over lo-owned pairs (psum'd)."""
    pi = costs.pair_pi(PairTable(lo=glo, hi=ghi, cnt=gcnt, valid=mine),
                       state.size)
    glo_c = jnp.clip(glo, 0, v - 1)
    ghi_c = jnp.clip(ghi, 0, v - 1)
    touched = (state.size[glo_c] > 1) | (state.size[ghi_c] > 1)
    decided = costs.keep_superedge(gcnt, pi, cbar, jnp.float32(log2v),
                                   cfg.re_guard)
    keep = jnp.where(touched, decided, gcnt > 0.0) & mine
    cntk = jnp.where(keep, gcnt, 0.0)
    sigma = jnp.where(keep, gcnt / jnp.maximum(pi, 1.0), 0.0)
    re1_local = jnp.sum(2.0 * cntk * (1.0 - sigma)) + jnp.sum(
        jnp.where(mine & ~keep, gcnt, 0.0))
    p_total = jax.lax.psum(jnp.sum(keep.astype(jnp.float32)), axis_names)
    w_total = jax.lax.pmax(jnp.max(cntk), axis_names)
    re1_total = _ordered_psum(re1_local, axis_names)
    log2s = jnp.log2(jnp.maximum(s_count, 2.0))
    log2w = jnp.log2(jnp.maximum(w_total, 2.0))
    size_bits = p_total * (2.0 * log2s + log2w) + v * log2s
    return {
        "size_bits": size_bits,
        "re1": 2.0 * re1_total / (float(v) * (v - 1.0)),
        "num_superedges": p_total,
        "num_supernodes": s_count,
        "nmerges": nmerges_g,
        "overflow": jax.lax.psum(overflow, axis_names),
    }


def _local_supernode_shingles(src_l, dst_l, node2super, h, num_nodes):
    """Per-supernode min-hash from the local edge shard (pmin-able)."""
    pad = src_l < 0
    s_safe = jnp.maximum(src_l, 0)
    d_safe = jnp.maximum(dst_l, 0)
    sent = jnp.int32(num_nodes)
    f = h  # closed neighborhood: own hash first
    f = f.at[jnp.where(pad, sent, s_safe)].min(
        jnp.where(pad, sent, h[d_safe]), mode="drop")
    f = f.at[jnp.where(pad, sent, d_safe)].min(
        jnp.where(pad, sent, h[s_safe]), mode="drop")
    out = jnp.full((num_nodes,), num_nodes, jnp.int32)
    out = out.at[node2super].min(f)
    return out


class DistributedBackend:
    """Engine :class:`~repro.core.engine.Backend` over an edge-sharded mesh.

    Built by :func:`make_distributed_backend`. Holds the jitted step /
    sparsify / chunk programs; call :meth:`bind` with the per-device edge
    shards before handing it to :class:`~repro.core.engine.SummaryEngine`.
    The raw programs remain addressable for direct use:

      * ``step(src_l, dst_l, state, θ, salt)`` — one merge round
        (``(..., groups_all)`` with ``external_groups=True``);
      * ``sparsify(src_l, dst_l, state, k_bits, salt)`` — Sect. 3.2.4 tail;
      * ``chunk(src_l, dst_l, state, θ[R], t0, k_bits, limit)`` — the
        device-resident multi-round driver.
    """

    stat_keys = DIST_STAT_KEYS

    def __init__(self, mesh, cfg: SummaryConfig, num_nodes: int,
                 num_edges: int, step, sparsify_fn, chunk):
        self.mesh = mesh
        self.cfg = cfg
        self.num_nodes = num_nodes
        self.num_edges = num_edges
        self.step = step
        self.sparsify = sparsify_fn
        self.chunk = chunk
        self._src = None
        self._dst = None

    def bind(self, src_p, dst_p) -> "DistributedBackend":
        """Attach the per-device edge shards the engine will drive over."""
        self._src, self._dst = src_p, dst_p
        return self

    def _shards(self):
        if self._src is None:
            raise ValueError("DistributedBackend: call bind(src_p, dst_p) "
                             "with edge shards before running the engine")
        return self._src, self._dst

    # ---- engine Backend protocol ---------------------------------------
    def input_size_bits(self) -> float:
        return 2.0 * self.num_edges * float(np.log2(max(self.num_nodes, 2)))

    def init(self) -> SummaryState:
        # placed as every chunk's output is, so the first chunk and the
        # later ones run one compiled program
        return jax.device_put(init_state(self.num_nodes, self.cfg.seed),
                              self.state_sharding())

    def run_chunk(self, state, thetas, t0, k_bits, limit):
        src_p, dst_p = self._shards()
        with self.mesh:
            return self.chunk(src_p, dst_p, state, thetas,
                              jnp.uint32(t0), jnp.float32(k_bits),
                              jnp.int32(limit))

    def num_supernodes(self, state) -> int:
        return int(jnp.sum(state.size > 0))

    def state_sharding(self):
        """Replicated placement on *this* mesh — restoring a checkpoint
        written on a different device count resolves here (DESIGN.md §13:
        reshard-on-load, no resharding pass)."""
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh,
                             make_rules(self.mesh, "summarize").replicated)

    def sparsify_finalize(self, state, k_bits, salt) -> dict:
        src_p, dst_p = self._shards()
        with self.mesh:
            stats, pairs = self.sparsify(src_p, dst_p, state,
                                         jnp.float32(k_bits),
                                         jnp.uint32(salt))
        return {"stats": stats, "pairs": pairs}


def make_distributed_backend(mesh, cfg: SummaryConfig, num_nodes: int,
                             num_edges_global: int, *,
                             grouping: str = "compact",
                             capacity_factor: float = 4.0,
                             lean_sort: bool = False,
                             external_groups: bool = False,
                             ) -> DistributedBackend:
    """Build the one edge-sharded backend for ``mesh`` (DESIGN.md §7/§12).

    ``grouping`` selects candidate-set ownership: ``"hash"`` (re-drawn
    supernode hash, [V, D] tables — fine through LiveJournal scale) or
    ``"compact"`` (group-owner sharding with compact tables — the web-scale
    path). ``lean_sort`` selects the 2-key grouping sort (§Perf ssumm
    iter. 1); ``external_groups`` makes the step take a precomputed
    ``groups_all`` ([G_pad, C], from :func:`make_grouping_fn`) as a sixth
    argument so the grouping can run every ``regroup_every``-th iteration
    (§Perf iter. C2). Inputs at call time: padded edge shards
    (int32[E_pad], -1 padding), replicated ``SummaryState``, θ scalar, and
    an ownership salt.
    """
    if grouping not in ("hash", "compact"):
        raise ValueError(f"unknown grouping {grouping!r}; "
                         f"valid: ['compact', 'hash']")
    rules = make_rules(mesh, "summarize")
    axis_names = rules.axis_names
    n_dev = rules.n_devices
    v = num_nodes
    c = cfg.group_size
    g_total = -(-v // c)
    g_pad = -(-g_total // n_dev) * n_dev
    g_own = g_pad // n_dev
    n_rows = g_own * c  # owned supernode slots per device (compact)
    log2v = float(np.log2(max(v, 2)))
    kernel = kops.resolve_kernel_backend(cfg.kernel_backend)

    def bucket_cap(e_loc: int) -> int:
        # a destination can never receive more records than the sender
        # has valid pairs (≤ e_loc), so capacity beyond e_loc is pure
        # bucket memory waste — at web/CI scale the uncapped factor
        # allocated multi-GB buckets for provably-empty slots
        return min(int(e_loc * capacity_factor / n_dev), e_loc) + 8

    def cbar_of(s_count, omega_all):
        if cfg.cbar_mode == "paper":
            return jnp.float32(2.0 * log2v
                               + float(np.log2(max(num_edges_global, 2))))
        return 2.0 * jnp.log2(s_count) + jnp.log2(
            jnp.maximum(omega_all, 2.0))

    # ---- one merge round, per-shard body --------------------------------
    def step_hash(src_l, dst_l, state: SummaryState, theta, salt):
        cap = bucket_cap(src_l.shape[0])
        plo, phi, cnt, valid = _local_pairs(src_l, dst_l, state.node2super, v)
        glo, ghi, gcnt, gvalid, overflow = _exchange(
            plo, phi, cnt, valid, rules.owner(plo, salt),
            rules.owner(phi, salt), axis_names, n_dev, cap, v)
        dev = jax.lax.axis_index(axis_names)

        s_count = jnp.maximum(jnp.sum(state.size > 0).astype(jnp.float32), 2.0)
        omega_all = jax.lax.pmax(jnp.max(jnp.where(gvalid, gcnt, 0.0)),
                                 axis_names)
        cbar = cbar_of(s_count, omega_all)

        owned = rules.owner(jnp.arange(v, dtype=jnp.int32), salt) == dev
        groups = shingles.build_groups_from_pairs(
            glo, ghi, gvalid, jnp.where(owned, state.size, 0),
            jax.random.fold_in(state.rng, dev), cfg.group_size,
        )
        pt = PairTable(lo=glo, hi=ghi, cnt=gcnt, valid=gvalid)
        gt = tables.build_group_tables(
            pt, state, groups, cfg.max_neighbors, cfg.union_size, cbar, v
        )
        rel, _ = kops.merge_gain(
            gt.m, gt.n, gt.s, gt.t, gt.n_u, gt.cidx, gt.w, cbar,
            jnp.float32(log2v), backend=kernel,
        )
        a, b, sel = select_matching(rel, gt.members, theta)
        # ownership discipline: only merges between two *owned* supernodes
        # are valid on this device — trailing groups may contain non-owned
        # (masked-dead) ids whose sizes are live in the shared tables.
        a_safe = jnp.clip(a, 0, v - 1)
        b_safe = jnp.clip(b, 0, v - 1)
        sel = sel & owned[a_safe] & owned[b_safe]
        a_all = jax.lax.all_gather(a, axis_names, tiled=True)
        b_all = jax.lax.all_gather(b, axis_names, tiled=True)
        sel_all = jax.lax.all_gather(sel, axis_names, tiled=True)
        new_state, nmerges_g = apply_merges(state, a_all, b_all, sel_all)

        mine = gvalid & (rules.owner(glo, salt) == dev)
        stats = _round_metrics(cfg, state, glo, ghi, gcnt, mine, cbar,
                               log2v, v, axis_names, s_count, nmerges_g,
                               overflow)
        new_state = SummaryState(
            node2super=new_state.node2super,
            size=new_state.size,
            rng=jax.random.fold_in(state.rng, 1729),
            t=state.t + 1,
        )
        return new_state, stats

    def step_compact(src_l, dst_l, state: SummaryState, theta, salt,
                     groups_in=None):
        del salt  # ownership re-randomizes through the shingle rng
        cap = bucket_cap(src_l.shape[0])
        dev = jax.lax.axis_index(axis_names)

        # ---- identical-everywhere candidate groups ----------------------
        k_h, k_tie, k_next = jax.random.split(state.rng, 3)
        if groups_in is not None:
            groups_all = groups_in
        else:
            h = jax.random.permutation(k_h, v).astype(jnp.int32)
            f_loc = _local_supernode_shingles(src_l, dst_l,
                                              state.node2super, h, v)
            f = jax.lax.pmin(f_loc, axis_names)
            if lean_sort:
                # dead ids already carry the sentinel shingle == V (§Perf)
                groups_all = shingles.chunk_groups_lean(f, c)
            else:
                groups_all = shingles.chunk_groups(f, state.size, k_tie, c)
            pad_rows = g_pad - groups_all.shape[0]
            if pad_rows:
                groups_all = jnp.concatenate(
                    [groups_all, jnp.full((pad_rows, c), -1, jnp.int32)])
        # device d owns groups ≡ d (mod n_dev)
        my_groups = jnp.take(
            groups_all.reshape(g_pad // n_dev, n_dev, c), dev, axis=1)

        # group-owner of every supernode id
        flat_members = groups_all.reshape(-1)
        gidx = jnp.arange(g_pad * c, dtype=jnp.int32) // c
        owner_of = jnp.zeros((v + 1,), jnp.int32).at[
            jnp.where(flat_members >= 0, flat_members, v)
        ].set(gidx % n_dev, mode="drop")[:-1]
        # owned-slot of every supernode id (-1 = not owned here)
        my_flat = my_groups.reshape(-1)
        slot_of = jnp.full((v + 1,), -1, jnp.int32).at[
            jnp.where(my_flat >= 0, my_flat, v)
        ].set(jnp.arange(n_rows, dtype=jnp.int32), mode="drop")[:-1]

        # ---- pair exchange to group owners ------------------------------
        plo, phi, cnt, valid = _local_pairs(src_l, dst_l, state.node2super, v)
        glo, ghi, gcnt, gvalid, overflow = _exchange(
            plo, phi, cnt, valid, owner_of[jnp.clip(plo, 0, v - 1)],
            owner_of[jnp.clip(phi, 0, v - 1)], axis_names, n_dev, cap, v)

        # ---- compact tables for owned groups -----------------------------
        s_count = jnp.maximum(jnp.sum(state.size > 0).astype(jnp.float32), 2.0)
        omega_all = jax.lax.pmax(jnp.max(jnp.where(gvalid, gcnt, 0.0)),
                                 axis_names)
        cbar = cbar_of(s_count, omega_all)

        nbr_id, nbr_cnt, self_cnt = tables.build_neighbor_tables_compact(
            glo, ghi, gcnt, gvalid, slot_of, n_rows, v, cfg.max_neighbors)
        t_all = tables.supernode_total_costs_compact(
            glo, ghi, gcnt, gvalid, slot_of, n_rows, v, state.size, cbar,
            jnp.float32(log2v))
        gt = tables.assemble_group_tables(
            nbr_id, nbr_cnt, self_cnt, t_all, state.size, my_groups,
            row_of_member=slot_of, union_size=cfg.union_size, num_nodes=v)
        rel, _ = kops.merge_gain(
            gt.m, gt.n, gt.s, gt.t, gt.n_u, gt.cidx, gt.w, cbar,
            jnp.float32(log2v), backend=kernel)
        a, b, sel = select_matching(rel, gt.members, theta)
        a_all = jax.lax.all_gather(a, axis_names, tiled=True)
        b_all = jax.lax.all_gather(b, axis_names, tiled=True)
        sel_all = jax.lax.all_gather(sel, axis_names, tiled=True)
        new_state, nmerges_g = apply_merges(state, a_all, b_all, sel_all)

        mine = gvalid & (owner_of[jnp.clip(glo, 0, v - 1)] == dev)
        stats = _round_metrics(cfg, state, glo, ghi, gcnt, mine, cbar,
                               log2v, v, axis_names, s_count, nmerges_g,
                               overflow)
        new_state = SummaryState(
            node2super=new_state.node2super, size=new_state.size,
            rng=k_next, t=state.t + 1)
        return new_state, stats

    step_shard = step_hash if grouping == "hash" else step_compact

    # ---- Sect. 3.2.4 further sparsification, per-shard body -------------
    def sparsify_shard(src_l, dst_l, state: SummaryState, k_bits, salt):
        cap = bucket_cap(src_l.shape[0])
        dev = jax.lax.axis_index(axis_names)

        # ---- pair exchange: each pair to its lo owner, counted once ------
        plo, phi, cnt, valid = _local_pairs(src_l, dst_l, state.node2super, v)
        glo, ghi, gcnt, gvalid, of = _exchange(
            plo, phi, cnt, valid, rules.owner(plo, salt), None,
            axis_names, n_dev, cap, v)
        mine = gvalid & (rules.owner(glo, salt) == dev)

        # ---- pre-drop metrics (identical to costs.summary_metrics) -------
        s_count = jnp.maximum(jnp.sum(state.size > 0).astype(jnp.float32), 2.0)
        pt = PairTable(lo=glo, hi=ghi, cnt=gcnt, valid=mine)
        pi = costs.pair_pi(pt, state.size)
        omega_all = jax.lax.pmax(jnp.max(jnp.where(mine, gcnt, 0.0)),
                                 axis_names)
        cbar = costs.cbar_value(cfg.cbar_mode, v, num_edges_global, s_count,
                                omega_all)
        glo_c = jnp.clip(glo, 0, v - 1)
        ghi_c = jnp.clip(ghi, 0, v - 1)
        touched = (state.size[glo_c] > 1) | (state.size[ghi_c] > 1)
        decided = costs.keep_superedge(gcnt, pi, cbar, jnp.float32(log2v),
                                       cfg.re_guard)
        keep = jnp.where(touched, decided, gcnt > 0.0) & mine
        cntk = jnp.where(keep, gcnt, 0.0)
        p_total = jax.lax.psum(jnp.sum(keep.astype(jnp.float32)), axis_names)
        w_total = jax.lax.pmax(jnp.max(cntk), axis_names)
        log2s = jnp.log2(jnp.maximum(s_count, 2.0))
        size_before = p_total * (2.0 * log2s
                                 + jnp.log2(jnp.maximum(w_total, 2.0))
                                 ) + v * log2s

        # ---- ξ and the distributed order statistic -----------------------
        delta = sparsify.sparsify_deltas(gcnt, pi, cfg.error_p)
        xi = sparsify.sparsify_xi(size_before, k_bits, s_count, w_total)
        delta_xi = sparsify.select_delta_xi(
            delta, keep, xi,
            reduce_hist=lambda h: jax.lax.psum(h, axis_names))
        drop = sparsify.drop_from_threshold(keep, delta, delta_xi, xi,
                                            p_total.astype(jnp.int32))

        # ---- post-drop metrics (Eq. 4 / Eq. 2 closed forms) --------------
        keep2 = keep & ~drop
        cntk2 = jnp.where(keep2, gcnt, 0.0)
        sigma2 = jnp.where(keep2, gcnt / jnp.maximum(pi, 1.0), 0.0)
        p2 = jax.lax.psum(jnp.sum(keep2.astype(jnp.float32)), axis_names)
        w2 = jax.lax.pmax(jnp.max(cntk2), axis_names)
        size_after = p2 * (2.0 * log2s + jnp.log2(jnp.maximum(w2, 2.0))
                           ) + v * log2s
        dropped_cnt = jnp.where(mine & ~keep2, gcnt, 0.0)
        re1_sum = _ordered_psum(
            jnp.sum(2.0 * cntk2 * (1.0 - sigma2)) + jnp.sum(dropped_cnt),
            axis_names)
        re2_sq = _ordered_psum(
            jnp.sum(cntk2 * (1.0 - sigma2)) + jnp.sum(dropped_cnt),
            axis_names)
        denom = float(v) * (v - 1.0)
        stats = {
            "size_bits": size_after,
            "size_bits_before": size_before,
            "re1": 2.0 * re1_sum / denom,
            "re2": jnp.sqrt(2.0 * re2_sq) / denom,
            "num_superedges": p2,
            "num_supernodes": s_count,
            "omega_max": w2,
            "xi": xi.astype(jnp.float32),
            "dropped": jax.lax.psum(jnp.sum(drop.astype(jnp.float32)),
                                    axis_names),
            "overflow": jax.lax.psum(of, axis_names),
        }
        pairs = {"lo": glo, "hi": ghi, "cnt": gcnt, "keep": keep2,
                 "drop": drop, "mine": mine}
        return stats, pairs

    # ---- device-resident chunked driver, per-shard body ------------------
    def chunk_shard(src_l, dst_l, state: SummaryState, thetas, t0, k_bits,
                    limit):
        r = thetas.shape[0]
        buf0 = {k: jnp.zeros((r,), jnp.float32) for k in DIST_STAT_KEYS}

        def cond(carry):
            i, _state, done, _buf = carry
            return (i < limit) & ~done

        def body(carry):
            i, state, _done, buf = carry
            theta = thetas[i]
            salt = t0 + i.astype(jnp.uint32)
            new_state, stats = step_shard(src_l, dst_l, state, theta, salt)
            buf = {
                k: buf[k].at[i].set(stats[k].astype(jnp.float32))
                for k in DIST_STAT_KEYS
            }
            done = (stats["size_bits"] <= k_bits) | (
                (stats["nmerges"] == 0) & (theta == 0.0)
            )
            return i + 1, new_state, done, buf

        rounds, state, _done, buf = jax.lax.while_loop(
            cond, body, (jnp.int32(0), state, jnp.bool_(False), buf0)
        )
        return state, buf, rounds

    spec_e = rules.edge_spec
    spec_r = rules.replicated
    if external_groups:
        if grouping != "compact":
            raise ValueError("external_groups requires grouping='compact'")

        def step_ext(src_l, dst_l, state, theta, salt, groups_all):
            return step_compact(src_l, dst_l, state, theta, salt, groups_all)

        step_sharded = shard_map(
            step_ext, mesh=mesh,
            in_specs=(spec_e, spec_e, spec_r, spec_r, spec_r, spec_r),
            out_specs=(spec_r, spec_r),
            check_vma=False,
        )
    else:
        step_sharded = shard_map(
            step_shard, mesh=mesh,
            in_specs=(spec_e, spec_e, spec_r, spec_r, spec_r),
            out_specs=(spec_r, spec_r),
            check_vma=False,
        )
    sparsify_sharded = shard_map(
        sparsify_shard, mesh=mesh,
        in_specs=(spec_e, spec_e, spec_r, spec_r, spec_r),
        out_specs=(spec_r, spec_e),
        check_vma=False,
    )
    chunk_sharded = shard_map(
        chunk_shard, mesh=mesh,
        in_specs=(spec_e, spec_e, spec_r, spec_r, spec_r, spec_r, spec_r),
        out_specs=(spec_r, spec_r, spec_r),
        check_vma=False,
    )
    return DistributedBackend(
        mesh, cfg, num_nodes, num_edges_global,
        step=jax.jit(step_sharded),
        sparsify_fn=jax.jit(sparsify_sharded),
        chunk=jax.jit(chunk_sharded),
    )


# ---------------------------------------------------------------------------
# Compat shims over the one backend builder
# ---------------------------------------------------------------------------


def make_distributed_step(mesh, cfg: SummaryConfig, num_nodes: int,
                          num_edges_global: int, capacity_factor: float = 4.0):
    """Compat shim: the hash-owner one-iteration step (backend ``.step``)."""
    return make_distributed_backend(
        mesh, cfg, num_nodes, num_edges_global, grouping="hash",
        capacity_factor=capacity_factor,
    ).step


def make_distributed_step_compact(mesh, cfg: SummaryConfig, num_nodes: int,
                                  num_edges_global: int,
                                  capacity_factor: float = 4.0,
                                  lean_sort: bool = False,
                                  external_groups: bool = False):
    """Compat shim: the group-owner (web-scale) step (backend ``.step``)."""
    return make_distributed_backend(
        mesh, cfg, num_nodes, num_edges_global, grouping="compact",
        capacity_factor=capacity_factor, lean_sort=lean_sort,
        external_groups=external_groups,
    ).step


def make_distributed_sparsify(mesh, cfg: SummaryConfig, num_nodes: int,
                              num_edges_global: int,
                              capacity_factor: float = 4.0):
    """Compat shim: the edge-sharded Sect. 3.2.4 phase (backend
    ``.sparsify``): ``(src_l, dst_l, state, k_bits, salt) → (stats, pairs)``
    with replicated ``stats`` and the still-sharded per-pair table."""
    return make_distributed_backend(
        mesh, cfg, num_nodes, num_edges_global, grouping="hash",
        capacity_factor=capacity_factor,
    ).sparsify


def pad_and_shard_edges(src, dst, mesh) -> tuple[jax.Array, jax.Array]:
    """Pad the edge list to a multiple of the device count (-1 padding).

    Compatibility shim over :func:`repro.graphs.feed.shard_edges` — the
    returned arrays are now *born sharded* per ``MeshRules.edge_spec``
    (identical contents to the historical full-host construction, but no
    full-|E| concatenate copy; DESIGN.md §11). Callers holding a CSR
    cache should feed it directly via
    :func:`repro.graphs.feed.shard_edges_from_cache` instead of
    densifying the mmap'd columns just to pass them here.
    """
    from repro.graphs.feed import shard_edges

    shards = shard_edges(src, dst, mesh)
    return shards.src, shards.dst


def make_grouping_fn(mesh, cfg: SummaryConfig, num_nodes: int,
                     lean_sort: bool = True):
    """Standalone candidate-grouping program (§Perf ssumm iteration C2).

    The grouping ([V]-sized shingle pmin + sort) is independent of the merge
    bookkeeping, so it can run every ``regroup_every``-th iteration and be
    amortized — the paper itself reuses candidate-set structure *within* an
    iteration (≤10 recursive re-splits before going random), so reusing a
    grouping for a small number of adjacent iterations is the same kind of
    coverage/efficiency trade, measured in EXPERIMENTS.md §Perf.

    Returns a jitted fn: (src_l, dst_l, state) → groups_all [G_pad, C]
    (replicated), with G padded to the mesh device count.
    """
    rules = make_rules(mesh, "summarize")
    axis_names = rules.axis_names
    n_dev = rules.n_devices
    v = num_nodes
    c = cfg.group_size
    g_total = -(-v // c)
    g_pad = -(-g_total // n_dev) * n_dev

    def fn(src_l, dst_l, state: SummaryState):
        k_h, k_tie, _ = jax.random.split(state.rng, 3)
        h = jax.random.permutation(k_h, v).astype(jnp.int32)
        f_loc = _local_supernode_shingles(src_l, dst_l, state.node2super, h, v)
        f = jax.lax.pmin(f_loc, axis_names)
        if lean_sort:
            groups_all = shingles.chunk_groups_lean(f, c)
        else:
            groups_all = shingles.chunk_groups(f, state.size, k_tie, c)
        pad_rows = g_pad - groups_all.shape[0]
        if pad_rows:
            groups_all = jnp.concatenate(
                [groups_all, jnp.full((pad_rows, c), -1, jnp.int32)])
        return groups_all

    spec_e = rules.edge_spec
    sharded = shard_map(
        fn, mesh=mesh, in_specs=(spec_e, spec_e, rules.replicated),
        out_specs=rules.replicated,
        check_vma=False,
    )
    return jax.jit(sharded)
