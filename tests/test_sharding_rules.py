"""MeshRules / make_rules: the edge-sharding table and the ownership hash.

Runs on the single CPU device (a 1×1 mesh exercises the full code path —
axis *names* are what the table is about, not axis sizes)."""

import jax
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import MeshRules, make_rules, owner_hash_np


def _mesh(axes=("data", "model")):
    devs = np.asarray(jax.devices()[:1]).reshape((1,) * len(axes))
    return Mesh(devs, axes)


def test_rules_build_on_any_mesh():
    for axes in (("data",), ("data", "model"), ("pod", "data", "model")):
        rules = make_rules(_mesh(axes))
        assert rules.n_devices == 1
        assert rules.axis_names == axes
        assert rules.edge_spec == P(axes[0] if len(axes) == 1 else axes)
        assert rules.replicated == P()


def test_summarize_mode_shards_edges_over_all_axes():
    rules = make_rules(_mesh())
    assert rules.edge_spec == P(("data", "model"))
    assert rules.replicated == P()


def test_owner_hash_np_matches_device_hash():
    """The host-side partition (owner_hash_np) and the device-side router
    (MeshRules.owner) must agree bit-for-bit, or the partitioned query
    tier would route probes to devices that do not hold the row."""
    import types

    import jax.numpy as jnp

    ids = np.arange(1024, dtype=np.int32)
    for n_dev in (1, 4, 8):
        mesh = types.SimpleNamespace(size=n_dev, axis_names=("data",))
        rules = MeshRules(mesh=mesh)
        for salt in (0, 1, 17, 2**31 - 1):
            want = np.asarray(rules.owner(jnp.asarray(ids),
                                          jnp.uint32(salt)))
            got = owner_hash_np(ids, salt, n_dev)
            assert np.array_equal(got, want), (n_dev, salt)
            assert got.min() >= 0 and got.max() < n_dev
