"""The reduction from a trace and a compiled program's HLO to the per-layer
metrics: on hand-made intervals, on a program compiled here, and on a small
trace recorded on a TPU v5 lite (``record_trace.py``)."""

import gzip
import importlib.util
import json
import os
import re
import shutil

import jax
import jax.numpy as jnp
import pytest

from harness import hlo, layers, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(os.path.dirname(DATA), "..", "metrics")


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (28, 29), (40, 50)]
    assert trace.union_length(iv, 0, 100) == 15 + 10 + 10
    assert trace.union_length(iv, 8, 45) == 7 + 10 + 5
    assert trace.gaps(iv, 0, 60) == [(15, 20), (30, 40), (50, 60)]
    assert trace.gaps(iv, 12, 25) == [(15, 20)]
    assert trace.gaps([], 3, 7) == [(3, 7)]


def test_instruction_names_and_opcodes():
    ev = trace.Event("%sort.299 = (s32[25819136]{0:T(1024)S(1)}, "
                     "f32[25819136]{0:T(1024)}) sort(s32[25819136]{0} %a, "
                     "f32[25819136]{0} %b), dimensions={0}", 0, 1)
    assert trace.instruction(ev) == ("sort.299", "sort")
    ev = trace.Event("%fusion.418 = f32[12909568]{0:T(1024)S(1)} fusion(f32"
                     "[12607,32,128]{0,1,2:T(8,128)} %x), kind=kCustom, "
                     "calls=%fused_computation.301", 0, 1)
    assert trace.instruction(ev) == ("fusion.418", "fusion")
    ev = trace.Event("%while.34 = (s32[], s32[403394]{0:T(1024)}) while("
                     "(s32[], s32[403394]) %t), condition=%c, body=%b", 0, 1)
    assert trace.instruction(ev)[1] in trace.CONTAINERS


def module_proto(compiled) -> bytes:
    """The serialized ``HloModuleProto`` of a compiled program."""
    mods = compiled.runtime_executable().hlo_modules()
    return mods[0].as_serialized_hlo_module_proto()


def test_fusions_are_classed_by_what_they_fuse():
    """A scatter and a sort inside fused or called computations are found
    from the compiled program's HloModuleProto."""
    def f(x, idx, keys):
        y = jnp.zeros(64).at[idx].add(x * 2.0 + 1.0)
        return y, jnp.sort(keys * 3)

    compiled = jax.jit(f).lower(jnp.ones(16), jnp.arange(16) % 64,
                                jnp.arange(32.0)).compile()
    table = hlo.parse_module(module_proto(compiled))
    with_scatter = [i for i, x in table.items() if "scatter" in x.ops]
    with_sort = [i for i, x in table.items() if "sort" in x.ops]
    assert with_scatter and with_sort
    # every instruction of the program's text is in the table
    names = re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", compiled.as_text(),
                       re.M)
    assert names and set(names) <= set(table)


def test_name_stack_reaches_the_instructions():
    """An op traced inside a nested jit keeps that jit in its scope."""
    inner = jax.jit(lambda x: jnp.log(x) * 2.0 + 1.0)

    def f(x):
        return jax.lax.fori_loop(0, 3, lambda i, y: inner(y) + y, x)

    jax.config.update("jax_traceback_in_locations_limit", 0)
    table = hlo.parse_module(module_proto(jax.jit(f).lower(
        jnp.ones(8)).compile()))
    assert any("jit(<lambda>)" in x.scope and "log" in x.ops
               for x in table.values())


def test_wire_format():
    """Varints, length-delimited fields and packed repeated integers."""
    # field 1 varint 300; field 2 bytes "hi"; field 38 packed [1, 150]
    msg = bytes([0x08, 0xAC, 0x02, 0x12, 0x02]) + b"hi" + bytes(
        [0xB2, 0x02, 0x03, 0x01, 0x96, 0x01])
    got = list(hlo.fields(msg))
    assert got[0] == (1, 300) and bytes(got[1][1]) == b"hi"
    assert got[2][0] == 38 and hlo._ints(got[2][1]) == [1, 150]


def test_opcode_of_tuple_shapes():
    assert trace.opcode_of("(s32[4]{0}, (f32[2], u32[])) tuple(%a, %b)") == \
        "tuple"
    assert trace.opcode_of("f32[8]{0} add(f32[8] %a, f32[8] %b)") == "add"


def load_metric(name):
    path = os.path.join(METRICS, name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".",
                                                                      "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Recorded:
    """What a traced run hands the metric readers, rebuilt from the
    recorded files."""

    def __init__(self, tmp_path):
        raw = tmp_path / "small.xplane.pb"
        with gzip.open(os.path.join(DATA, "small.xplane.pb.gz"), "rb") as f, \
                open(raw, "wb") as g:
            shutil.copyfileobj(f, g)
        self.profile = trace.Profile.load(str(raw))
        with open(os.path.join(DATA, "small_run.json")) as f:
            self.saved = json.load(f)
        self.merge_gain = dict(self.saved["merge_gain"])
        self.busy_s, self.window_s = self.profile.busy_window("bench.window")
        self.jobs = []

    def peaks(self):
        from harness.roofline import peaks

        return peaks(self.saved["device"]["kind"])


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    return Recorded(tmp_path_factory.mktemp("trace"))


def test_recorded_trace_window(recorded):
    assert len(recorded.profile.chips) == 1
    assert 0 < recorded.busy_s <= recorded.window_s
    assert recorded.busy_s == pytest.approx(recorded.saved["busy_s"])
    every = recorded.profile.op_seconds("bench.window", lambda op: True)
    # leaf ops do not overlap on one core: their sum is the busy time
    assert every == pytest.approx(recorded.busy_s, rel=1e-6)
    b = recorded.profile.breakdown("bench.window")
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(k, str) and v > 0 for k, v in b["device_ops"])


@pytest.mark.parametrize("name", ["sort_share", "scatter_share",
                                  "merge_gain_ms",
                                  "merge_gain_roofline",
                                  "device_idle_share.summarize"])
def test_recorded_metrics(recorded, name):
    value = load_metric(name).read(recorded)
    assert value == pytest.approx(recorded.saved["metrics"][name]["value"])
    if name.endswith(("share", "roofline", ".summarize")):
        assert 0 <= value <= 100
    assert value > 0 or name == "scatter_share"


def test_recorded_programs_have_sorts_and_scatters(recorded):
    """The trace carries the HLO of the programs that ran, under the names
    of their runs, and every op of the window is described by it."""
    runs = [m for m in recorded.profile.programs
            if m.startswith("jit__local_chunk(")]
    assert len(runs) == 1
    chunk = recorded.profile.programs[runs[0]]
    assert any("sort" in x.ops for x in chunk.values())
    assert any("scatter" in x.ops for x in chunk.values())
    # the name stack reaches the merge gain inside the rounds
    assert any("jit(merge_gain)" in x.scope for x in chunk.values())
    assert layers.unclassified_share(recorded.profile,
                                     recorded.busy_s) == 0.0


def test_ops_no_program_describes_are_counted(recorded):
    """Without the programs' HLO, every op is unclassified, and no share
    counts it as a sort or a scatter."""
    import dataclasses

    bare = dataclasses.replace(recorded.profile, programs={})
    assert layers.unclassified_share(bare, recorded.busy_s) == \
        pytest.approx(100.0)

    class Run:
        profile, busy_s = bare, recorded.busy_s

    assert layers.share_of_busy(Run, lambda instr: True) is None
