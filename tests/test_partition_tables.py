"""Host-side properties of the partitioned tier's halo index tables.

The partitioned engine's correctness rests on two invariants of
:func:`repro.core.queries_jax.build_partition_tables` that the device
kernels cannot re-check at runtime:

  * **coverage** — on the device that owns a row, every (row, referenced
    column) pair resolves through owned ∪ halo storage: the share map
    covers all references (owned position or halo slot, never the
    sentinel), and the row map covers them through owned / resident-halo /
    dense-slab storage — the second-hop fallback is exactly the dense
    remainder, nothing leaks;
  * **determinism** — tables are a pure function of (summary, owner,
    device count, dense threshold): rebuilds (elastic re-mesh) must
    reproduce them bit-for-bit.

(The owner_hash_np ↔ MeshRules.owner bit-equivalence that makes the
host partition agree with device routing lives in
tests/test_sharding_rules.py next to the rest of the rules table.)
"""

import numpy as np
import pytest

from repro.core import queries as Q
from repro.core.queries_jax import build_partition_tables
from repro.dist.sharding import owner_hash_np
from test_queries_jax import _random_summary


def _tables_for(rng, n_dev: int, dense_row_nnz=None):
    res = _random_summary(rng, v_max=40)
    bs = Q.build_block_summary(res)
    owner = owner_hash_np(bs.ids, int(rng.integers(0, 1000)), n_dev)
    return bs, owner, build_partition_tables(bs, owner, n_dev,
                                             dense_row_nnz)


@pytest.mark.parametrize("n_dev", [1, 3, 8])
@pytest.mark.parametrize("dense_row_nnz", [None, 0, 2])
def test_halo_coverage(n_dev, dense_row_nnz):
    rng = np.random.default_rng(100 * n_dev + (dense_row_nnz or 7))
    for _ in range(10):
        bs, owner, t = _tables_for(rng, n_dev, dense_row_nnz)
        s_own, h = t.own_gids.shape[1], t.halo_gids.shape[1]
        ht = t.row_halo_gids.shape[1]
        dmax = t.dense_slots.shape[1]
        share_sent = s_own + h
        row_sent = s_own + ht + n_dev * dmax
        for q in range(n_dev):
            own = t.own_gids[q][t.own_gids[q] >= 0]
            assert np.array_equal(own, np.flatnonzero(owner == q))
            # the owned entries are the owned rows' CSR entries, row-major
            n_ent = int(t.own_indptr[q, -1])
            ent = t.own_ent[q, :n_ent]
            want = [np.arange(bs.indptr[g], bs.indptr[g + 1]) for g in own]
            assert np.array_equal(ent, np.concatenate(want + [[]]))
            assert np.all(t.own_ent[q, n_ent:] == -1)
            assert np.array_equal(np.diff(t.own_indptr[q, :own.size + 1]),
                                  np.diff(bs.indptr)[own])
            # every real reference resolves below the sentinel; every
            # padding entry resolves TO the sentinel
            assert np.all(t.loc_share[q, :n_ent] < share_sent)
            assert np.all(t.loc_share[q, n_ent:] == share_sent)
            assert np.all(t.loc_row[q, :n_ent] < row_sent)
            assert np.all(t.loc_row[q, n_ent:] == row_sent)
            # ... and to the entry's own column
            ext_share = np.concatenate([t.own_gids[q], t.halo_gids[q]])
            assert np.array_equal(ext_share[t.loc_share[q, :n_ent]],
                                  bs.cols[ent])
            ext_row = np.concatenate([t.own_gids[q], t.row_halo_gids[q],
                                      t.dense_slots.ravel()])
            assert np.array_equal(ext_row[t.loc_row[q, :n_ent]],
                                  bs.cols[ent])
            # the share-side halo is exactly the remote referenced blocks
            refs = np.unique(bs.cols[ent])
            remote = refs[owner[refs] != q]
            assert np.array_equal(t.halo_gids[q][t.halo_gids[q] >= 0],
                                  remote)
            # halo coordinates point at the true owner slot
            hl = t.halo_gids[q][t.halo_gids[q] >= 0]
            assert np.array_equal(t.halo_src_dev[q, :hl.size], owner[hl])
            assert np.array_equal(
                t.halo_src_pos[q, :hl.size], t.block_pos[hl])
            # the row-side resident halo + dense slab partition the
            # remote references (the second-hop route is exactly the
            # dense remainder)
            dense = np.isin(remote, t.dense_gids)
            assert np.array_equal(
                t.row_halo_gids[q][t.row_halo_gids[q] >= 0],
                remote[~dense])


def test_tables_deterministic_across_rebuilds():
    rng = np.random.default_rng(5)
    res = _random_summary(rng, v_max=40)
    bs = Q.build_block_summary(res)
    owner = owner_hash_np(bs.ids, 17, 8)
    a = build_partition_tables(bs, owner, 8, dense_row_nnz=2)
    b = build_partition_tables(bs, owner, 8, dense_row_nnz=2)
    for name in ("owner", "block_pos", "own_gids", "own_ent",
                 "own_indptr", "halo_gids",
                 "halo_src_dev", "halo_src_pos", "row_halo_gids",
                 "dense_gids", "dense_slots", "loc_share", "loc_row"):
        assert np.array_equal(getattr(a, name), getattr(b, name)), name


def test_dense_threshold_moves_rows_to_second_hop():
    rng = np.random.default_rng(9)
    bs, owner, t_all = _tables_for(rng, 4, dense_row_nnz=None)
    t_cut = build_partition_tables(bs, owner, 4, dense_row_nnz=0)
    row_nnz = np.diff(bs.indptr)
    assert np.array_equal(t_cut.dense_gids, np.flatnonzero(row_nnz > 0))
    assert t_all.dense_gids.size == 0
    # with every nonempty row dense, resident row-halos are empty
    assert np.all(t_cut.row_halo_gids < 0) or np.all(
        row_nnz[t_cut.row_halo_gids[t_cut.row_halo_gids >= 0]] == 0)
