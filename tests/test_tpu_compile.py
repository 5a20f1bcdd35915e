"""The main path's Pallas kernels compile for a TPU v5e at amazon0601 size,
and the partitioned query tier compiles over a v5e 2x2 mesh.

Nothing runs: the TPU compiler, installed with jax, compiles for a v5e
that is described, not attached, so this guards the Mosaic layout rules
(block shapes, 2-D values, SMEM scalars) that interpret mode never checks,
and the collectives the TPU supports (it all-reduces float64 only by sum).
A whole ``merge.merge_iteration`` at these shapes is left out: its sorts
take minutes to compile for the TPU. The merge round is compiled at a tiny
size instead, for the names its layers keep through the TPU compiler, the
group tables' assembly alone at the benchmark cells' shapes, and the pair
table alone at a small one.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.kernels.entropy_bits import pair_cost_pallas
from repro.kernels.merge_gain import merge_gain_pallas

V, E = 403_394, 2_419_961  # amazon0601 stand-in after canonicalization
C, U = 32, 128  # SummaryConfig.group_size, .union_size
G = -(-V // C)  # 12,607 candidate groups


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 (libtpu loads only in this fixture).

    The persistent compilation cache is off meanwhile: an executable for a
    described chip is written to it but cannot be read back without one."""
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_merge_gain_compiles_for_v5e(one_chip):
    f32, i32 = jnp.float32, jnp.int32
    compiled = _compile(
        functools.partial(merge_gain_pallas, interpret=False), one_chip,
        ((G, C, U), f32), ((G, C), f32), ((G, C), f32), ((G, C), f32),
        ((G, U), f32), ((G, C), i32), ((G, C, C), f32), ((), f32), ((), f32))
    assert "tpu_custom_call" in compiled.as_text()
    # found by its stable name inside the ``merge_gain`` scope
    assert "ssumm_merge_gain" in compiled.as_text()


def test_pair_cost_compiles_for_v5e(one_chip):
    f32 = jnp.float32
    compiled = _compile(
        functools.partial(pair_cost_pallas, interpret=False), one_chip,
        ((E,), f32), ((E,), f32), ((), f32), ((), f32))
    assert "tpu_custom_call" in compiled.as_text()


def test_group_tables_compile_without_scatter_for_v5e(one_chip):
    """The union space at the benchmark cells' shapes is built by sorts and
    compare-reduces: no scatter, and the [G, C, D, U] compare is fused into
    its sum (held in memory it alone would be 4.3 GB of temporaries)."""
    from repro.core.tables import assemble_group_tables

    v, d, g, c, u = 131_072, 64, 4_096, 32, 128
    f32, i32 = jnp.float32, jnp.int32
    compiled = _compile(
        functools.partial(assemble_group_tables, row_of_member=None,
                          union_size=u, num_nodes=v), one_chip,
        ((v, d), i32), ((v, d), f32), ((v,), f32), ((v,), f32), ((v,), i32),
        ((g, c), i32))
    assert not re.search(r"\sscatter\(", compiled.as_text())
    # the temporaries of the scatter form it replaced, for the same v5e
    assert compiled.memory_analysis().temp_size_in_bytes <= 147_219_456


def test_pair_table_compiles_without_scatter_for_v5e(one_chip):
    """The pair table is one two-key sort kept in sorted order, with its run
    lengths from a reverse cumulative min: no scatter compacts the runs.
    Small shapes: at the benchmark cells' E the compile takes a minute."""
    from repro.core.costs import build_pair_table
    from repro.core.types import SummaryState

    v, e = 1_024, 8_192
    i32 = jnp.int32

    def pair_table(src, dst, node2super):
        state = SummaryState(node2super=node2super, size=jnp.ones((v,), i32),
                             rng=jnp.zeros((2,), jnp.uint32), t=jnp.int32(1))
        return build_pair_table(src, dst, state)

    text = _compile(pair_table, one_chip, ((e,), i32), ((e,), i32),
                    ((v,), i32)).as_text()
    assert re.search(r"\ssort\(", text)
    assert not re.search(r"\sscatter\(", text)


def test_round_keeps_its_layer_names_on_v5e(one_chip):
    """Every sort and scatter of the merge round carries one of the round's
    named scopes after the TPU compiler's rewrites, which drop the name
    stack of a scatter over several indices."""
    from repro.core import SummaryConfig
    from repro.core.engine import _local_chunk
    from repro.core.types import SummaryState

    v, e = 256, 1500
    cfg = SummaryConfig(T=4, k_frac=0.3)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state = SummaryState(node2super=shape((v,), jnp.int32),
                         size=shape((v,), jnp.int32),
                         rng=shape(key.shape, key.dtype),
                         t=shape((), jnp.int32))
    text = _local_chunk.lower(
        shape((e,), jnp.int32), shape((e,), jnp.int32), state,
        shape((cfg.driver_chunk,), jnp.float32), shape((), jnp.float32),
        shape((), jnp.int32), cfg).compile().as_text()
    layers = {"pair_table", "summary_metrics", "shingles", "group_tables",
              "merge_gain", "matching"}
    named = []
    for line in text.splitlines():
        if re.search(r"\s(sort|scatter)\(", line.split("metadata=")[0]):
            scope = re.search(r'op_name="([^"]*)"', line)
            named.append(bool(scope) and bool(
                layers & set(scope.group(1).split("/"))))
    assert named and all(named)


def test_partitioned_queries_compile_for_v5e_2x2(topo, monkeypatch):
    from repro.core import queries_jax as QJ
    from test_queries_jax import _random_summary

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    rep = NamedSharding(mesh, PartitionSpec())
    # a described chip holds no arrays: the engine's leaves become shapes
    monkeypatch.setattr(jax, "device_put", lambda x, sh: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sh))
    eng = QJ.PartitionedQueryEngine(
        _random_summary(np.random.default_rng(0)), mesh)
    s, b = eng.tables.s, 8

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=rep)

    batch = (shape((b,), jnp.int32),) * 3 + (shape((s,), jnp.float64),
                                             shape((), jnp.float64))
    sets = (shape((b, s), jnp.float64),) * 3
    with QJ.enable_x64(), mesh:
        for program, args in ((eng._pagerank, ()), (eng._triangle, ()),
                              (eng._answer, batch),
                              (eng._answer_full, batch + sets)):
            compiled = program.lower(eng.part, eng.rep, *args).compile()
            assert compiled.as_text()
