"""The program names its layers on a profiler trace.

Device layers are ``jax.named_scope``s, so they reach every instruction's
``op_name`` in the compiled programs; host phases are ``TraceAnnotation``
spans on the trace's clock. Neither may change a result.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import SummaryConfig, summarize
from repro.core.engine import LocalBackend, _local_chunk, _local_finalize
from repro.graphs import generate

ROUND_SCOPES = {"pair_table", "summary_metrics", "shingles", "group_tables",
                "merge_gain", "matching"}
HOST_SPANS = ["ssumm.make_graph", "ssumm.engine", "ssumm.result"]
CFG = SummaryConfig(T=4, k_frac=0.3, seed=3)

#: ``%name = <shape> <opcode>(...)`` of one line of a program's HLO text
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = .*?\s([a-z][a-z\-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def graph():
    return generate("ego-facebook", seed=0, scale=0.05)


def heavy_ops(hlo_text: str, prefix: str) -> list[tuple[str, str]]:
    """``(opcode, op_name)`` of every sort, scatter and gather whose name
    stack starts with ``prefix``."""
    out = []
    for line in hlo_text.splitlines():
        m, name = INSTR.match(line), OP_NAME.search(line)
        if m and name and m.group(1) in ("sort", "scatter", "gather") \
                and name.group(1).startswith(prefix):
            out.append((m.group(1), name.group(1)))
    return out


@pytest.fixture(scope="module")
def backend():
    src, dst, v = graph()
    return LocalBackend(src, dst, v, CFG)


def test_round_ops_carry_a_layer_scope(backend):
    state = backend.init()
    compiled = _local_chunk.lower(
        backend.graph.src, backend.graph.dst, state,
        jnp.zeros((CFG.driver_chunk,), jnp.float32), jnp.float32(1.0),
        jnp.int32(1), CFG).compile()
    ops = heavy_ops(compiled.as_text(), "jit(_local_chunk)/while/body/")
    assert {op for op, _ in ops} == {"sort", "scatter", "gather"}
    unnamed = [(op, name) for op, name in ops
               if not ROUND_SCOPES & set(name.split("/"))]
    assert not unnamed
    # the merge gain's own scope sits inside its jit, on both backends
    text = compiled.as_text()
    assert "jit(merge_gain)/merge_gain/" in text


def test_finalize_selection_carries_sparsify(backend):
    compiled = _local_finalize.lower(
        backend.graph.src, backend.graph.dst, backend.init(),
        jnp.float32(1.0), CFG, backend.num_nodes, backend.num_edges).compile()
    ops = heavy_ops(compiled.as_text(), "jit(_local_finalize)/")
    top = {name.split("/")[1] for _, name in ops}
    assert top == {"pair_table", "sparsify"}
    assert any(op == "sort" and name.startswith("jit(_local_finalize)/"
                                                "sparsify/")
               for op, name in ops)


def host_spans(trace_dir) -> list[tuple[str, int, int]]:
    """``(name, start, end)`` of every ``ssumm.*`` host span in the trace."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events if e.name.startswith("ssumm.")]
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two ``summarize`` calls under the profiler, and one without."""
    src, dst, v = graph()
    plain = summarize(src, dst, v, CFG)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=options):
        under = [summarize(src, dst, v, CFG) for _ in range(2)]
    return plain, under, host_spans(trace_dir)


def test_host_spans_once_per_call_in_order(traced):
    _, under, spans = traced
    assert [s[0] for s in spans] == HOST_SPANS * len(under)
    for (_, _, end), (_, start, _) in zip(spans, spans[1:]):
        assert end <= start
    assert all(end > start for _, start, end in spans)


def test_results_identical_under_the_profiler(traced):
    plain, under, _ = traced
    for res in under:
        for field in ("node2super", "super_size", "edge_lo", "edge_hi",
                      "edge_w"):
            np.testing.assert_array_equal(getattr(res, field),
                                          getattr(plain, field))
        for field in ("size_bits", "re1", "re2", "mdl_cost",
                      "num_supernodes", "num_superedges", "iterations_run"):
            assert getattr(res, field) == getattr(plain, field)
        assert res.history == plain.history


def test_history_rows_hold_no_timings(traced):
    plain, _, _ = traced
    assert plain.history
    for row in plain.history:
        assert set(row) == set(LocalBackend.stat_keys) | {"t", "theta"}
