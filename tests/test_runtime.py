"""Runtime substrate: checkpoint atomicity/restore, elastic mesh planning,
straggler detection."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.runtime import CheckpointManager, StragglerMonitor, plan_mesh


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32),
        "nested": {"b": jnp.asarray(rng.standard_normal(3), jnp.float32),
                   "step": jnp.asarray(7, jnp.int32)},
    }


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = _tree()
    mgr.save(10, tree, extra={"loss": 1.5})
    got, step, extra = mgr.restore(jax.tree.map(jnp.zeros_like, tree))
    assert step == 10 and extra["loss"] == 1.5
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b), got, tree)


def test_checkpoint_keep_n_and_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save_async(s, _tree(s))
    mgr.wait()
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_ignores_uncommitted(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(5, _tree())
    # simulate a crash mid-write: directory without COMMIT
    os.makedirs(tmp_path / "step_0000000009")
    assert mgr.latest_step() == 5


def test_checkpoint_restore_shape_mismatch(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.zeros((2, 2))})
    with pytest.raises(ValueError):
        mgr.restore({"w": jnp.zeros((3, 3))})


def test_checkpoint_tmp_dir_ignored_and_gced(tmp_path):
    """A crash mid-write leaves only a ``.tmp-`` dir: restore never sees
    it, and the next successful save garbage-collects it."""
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(4, _tree())
    # simulate a writer killed between makedirs and the COMMIT fsync
    junk = tmp_path / ".tmp-9"
    junk.mkdir()
    (junk / "w.npy").write_bytes(b"partial garbage")
    assert mgr.latest_step() == 4
    got, step, _ = mgr.restore(jax.tree.map(jnp.zeros_like, _tree()))
    assert step == 4
    mgr.save(5, _tree(1))
    assert not junk.exists()
    assert mgr.all_steps() == [4, 5]


def test_checkpoint_save_stats_and_step_bytes(tmp_path):
    """Per-step accounting feeds the EXPERIMENTS §Resume overhead table:
    snapshot time (what the driver pays), write time, committed bytes."""
    mgr = CheckpointManager(str(tmp_path), keep=3)
    tree = _tree()
    mgr.save_async(2, tree, extra={"t_next": 3})
    mgr.wait()
    st = mgr.save_stats[2]
    assert st["snapshot_wall_s"] > 0.0
    assert st["write_wall_s"] > 0.0
    assert st["bytes"] == mgr.step_bytes(2) > 0
    # payload bytes dominate: every leaf is on disk
    leaf_bytes = sum(np.asarray(v).nbytes for v in jax.tree.leaves(tree))
    assert st["bytes"] > leaf_bytes
    assert mgr.step_bytes(99) == 0  # absent step


# ---------------------------------------------------------------------------
# elastic planning
# ---------------------------------------------------------------------------


@given(n=st.integers(1, 4096), batch=st.sampled_from([32, 256, 1024]))
@settings(max_examples=50, deadline=None)
def test_plan_mesh_properties(n, batch):
    plan = plan_mesh(n, global_batch=batch, want_model=16, want_pods=2)
    assert plan.n_devices == n
    assert "model" in plan.axes and "data" in plan.axes
    # model axis never exceeds the requested TP degree
    model = plan.shape[plan.axes.index("model")]
    assert model <= 16
    # global batch is preserved: dp · per_device · accum ≥ batch
    dp = plan.n_devices // model
    assert dp * plan.per_device_batch * plan.accum_steps >= min(batch, dp)


def test_plan_mesh_survivor_shrink():
    full = plan_mesh(512, global_batch=256, want_model=16, want_pods=2)
    assert full.shape == (2, 16, 16)
    survivor = plan_mesh(448, global_batch=256, want_model=16, want_pods=2)
    assert survivor.n_devices == 448  # keeps every surviving chip busy
    model = survivor.shape[survivor.axes.index("model")]
    assert 448 % model == 0


# ---------------------------------------------------------------------------
# straggler monitor
# ---------------------------------------------------------------------------


def test_straggler_flags_spike():
    mon = StragglerMonitor(warmup_steps=3, z_threshold=3.0, ratio_threshold=1.5)
    flags = [mon.observe(i, 0.1 + 0.001 * (i % 3)) for i in range(20)]
    assert not any(flags)
    assert mon.observe(20, 1.0)  # 10× spike
    assert len(mon.events) == 1 and mon.events[0].ratio > 5
    # EMA not polluted by the spike
    assert mon.mean < 0.2


def test_straggler_callback():
    mon = StragglerMonitor(warmup_steps=2, z_threshold=2.0, ratio_threshold=1.5)
    seen = []
    mon.on_straggler(seen.append)
    for i in range(10):
        mon.observe(i, 0.05)
    mon.observe(10, 0.5)
    assert len(seen) == 1 and seen[0].step == 10
