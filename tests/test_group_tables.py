"""``tables.assemble_group_tables`` against a plain numpy reference of the
union-space semantics (DESIGN.md §5).

Per group: the union is the group's distinct neighbour ids in ascending
order, cut at ``U`` columns and padded with ``V``; ``m[r, j]`` sums member
``r``'s counts of the id in column ``j``; ``cidx`` is a member's own column
(``U`` when outside the union or dead); ``w`` is the larger of the two
members' counts of each other, read through ``cidx``; ``n_u`` is the size of
each union supernode (0 for padding).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tables

V, D, G, C = 300, 8, 6, 8


def reference(nbr_id, nbr_cnt, self_cnt, t_all, sizes, groups, row_of, u):
    v = sizes.shape[0]
    g_cnt, c = groups.shape
    m = np.zeros((g_cnt, c, u), np.float32)
    uid = np.full((g_cnt, u), v, np.int64)
    n = np.zeros((g_cnt, c), np.float32)
    s = np.zeros((g_cnt, c), np.float32)
    t = np.zeros((g_cnt, c), np.float32)
    cidx = np.full((g_cnt, c), u, np.int32)
    w = np.zeros((g_cnt, c, c), np.float32)
    for g in range(g_cnt):
        rows = {}
        for r, member in enumerate(groups[g]):
            if member < 0 or sizes[member] == 0:
                continue
            row = member if row_of is None else row_of[member]
            if row < 0:
                continue
            rows[r] = row
            n[g, r] = sizes[member]
            s[g, r] = self_cnt[row]
            t[g, r] = t_all[row]
        ids = sorted({int(i) for row in rows.values()
                      for i in nbr_id[row] if i < v})[:u]
        uid[g, :len(ids)] = ids
        col = {i: j for j, i in enumerate(ids)}
        for r, row in rows.items():
            for i, k in zip(nbr_id[row], nbr_cnt[row]):
                if int(i) in col:
                    m[g, r, col[int(i)]] += k
            cidx[g, r] = col.get(int(groups[g, r]), u)
        for a in range(c):
            for b in range(c):
                ab = m[g, a, cidx[g, b]] if cidx[g, b] < u else 0.0
                ba = m[g, b, cidx[g, a]] if cidx[g, a] < u else 0.0
                w[g, a, b] = max(ab, ba)
    n_u = np.where(uid < v, sizes[np.minimum(uid, v - 1)], 0)
    return dict(m=m, n=n, s=s, t=t, n_u=n_u.astype(np.float32), cidx=cidx,
                w=w, members=groups)


def _tables(rng, n_rows, fill, repeat=False):
    nbr_id = np.full((n_rows, D), V, np.int32)
    nbr_cnt = np.zeros((n_rows, D), np.float32)
    for r in range(n_rows):
        k = int(rng.integers(0, fill + 1))
        nbr_id[r, :k] = rng.choice(V, size=k, replace=False)
        nbr_cnt[r, :k] = rng.integers(1, 9, size=k)
        if repeat and k > 1:
            nbr_id[r, k - 1] = nbr_id[r, 0]
    return nbr_id, nbr_cnt


def _case(name, seed):
    rng = np.random.default_rng(seed)
    u = {"under_u": 96, "over_u": 16}.get(name, 32)
    groups = rng.permutation(V)[:G * C].astype(np.int32).reshape(G, C)
    row_of = None
    n_rows = V
    if name == "row_of_member":  # half the members own a row, in any order
        n_rows = G * C // 2
        row_of = np.full(V, -1, np.int32)
        row_of[rng.choice(groups.ravel(), size=n_rows, replace=False)] = \
            rng.permutation(n_rows)
    nbr_id, nbr_cnt = _tables(rng, n_rows, fill=3 if name == "under_u" else D,
                              repeat=name == "repeated_id")
    # a group's members neighbour each other, so ``cidx`` and ``w`` are
    # exercised
    for g in range(G):
        for member in groups[g]:
            row = member if row_of is None else row_of[member]
            if row >= 0 and nbr_id[row, 0] < V:
                nbr_id[row, 0] = rng.choice(groups[g][groups[g] != member])
    sizes = rng.integers(1, 4, V).astype(np.int32)
    if name == "dead_and_padding":
        sizes[groups[:, 1]] = 0
        groups[:, -2:] = -1
        groups[0, :] = -1
    self_cnt = rng.integers(0, 4, n_rows).astype(np.float32)
    t_all = rng.random(n_rows).astype(np.float32)
    return nbr_id, nbr_cnt, self_cnt, t_all, sizes, groups, row_of, u


@pytest.mark.parametrize("name", ["under_u", "over_u", "dead_and_padding",
                                  "row_of_member", "repeated_id"])
def test_assemble_group_tables_matches_reference(name):
    nbr_id, nbr_cnt, self_cnt, t_all, sizes, groups, row_of, u = _case(name, 14)
    ref = reference(nbr_id, nbr_cnt, self_cnt, t_all, sizes, groups, row_of, u)
    gt = tables.assemble_group_tables(
        *(jnp.asarray(x) for x in (nbr_id, nbr_cnt, self_cnt, t_all, sizes,
                                   groups)),
        row_of_member=None if row_of is None else jnp.asarray(row_of),
        union_size=u, num_nodes=V)
    for field, want in ref.items():
        got = np.asarray(getattr(gt, field))
        assert got.shape == want.shape, field
        np.testing.assert_array_equal(got, want, err_msg=field)

    # each case holds what it is named for
    distinct = (ref["n_u"] > 0).sum(axis=1)
    if name == "under_u":
        assert 0 < distinct.max() < u
    if name == "over_u":
        assert (distinct == u).any()
    if name == "dead_and_padding":
        assert (groups == -1).any() and ((groups >= 0) & (ref["n"] == 0)).any()
    if name == "row_of_member":
        assert ((groups >= 0) & (row_of[np.maximum(groups, 0)] < 0)).any()
    if name == "repeated_id":
        assert any(len(set(r[r < V])) < (r < V).sum() for r in nbr_id)
    assert (ref["cidx"] < u).any() and (ref["w"] > 0).any()
