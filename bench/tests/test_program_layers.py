"""The readers of the program's own layer names (``harness/program_layers.py``
and the ten metrics that use it), on a hand-made ``trace.Profile``: device
ops whose instructions carry name stacks, and host spans."""

import importlib.util
import os
import types

import pytest

from harness import hlo, program_layers, trace

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
ROUND = ["pair_table_ms", "summary_metrics_ms", "shingles_ms",
         "group_tables_ms", "merge_gain_round_ms", "matching_ms"]
HOST = ["make_graph_ms", "engine_idle_ms", "result_ms"]
ALL = ROUND + ["sparsify_ms"] + HOST

CHUNK, FIN, MG = ("jit__local_chunk(1)", "jit__local_finalize(2)",
                  "jit_merge_gain(3)")
BODY = "jit(_local_chunk)/while/body/"
SCOPES = {
    CHUNK: {
        "sort.1": BODY + "pair_table/jit(sort)/sort",
        "fusion.2": BODY + "summary_metrics/reduce_sum",
        "fusion.3": BODY + "shingles/jit(_shuffle)/sort",
        "fusion.4": BODY + "group_tables/scatter-add",
        "fusion.5": BODY + "jit(merge_gain)/merge_gain/mul",
        "fusion.6": BODY + "matching/gather",
        # a layer nested in another counts for the outer one
        "fusion.7": BODY + "matching/pair_table/add",
        # in no layer: left out of every round metric
        "fusion.8": BODY + "dynamic_update_slice",
    },
    FIN: {
        "sort.1": "jit(_local_finalize)/pair_table/sort",
        "sort.2": "jit(_local_finalize)/sparsify/jit(sort)/sort",
        "fusion.3": "jit(_local_finalize)/sparsify/summary_metrics/reduce",
    },
    MG: {"fusion.1": "jit(merge_gain)/merge_gain/mul"},
}
MS = 1_000_000  # ns
#: (program, instruction, start ms, end ms): two jobs in the window, then
#: the benchmark's own merge-gain call after it
OPS = [
    (CHUNK, "sort.1", 5, 8), (CHUNK, "fusion.2", 8, 9),
    (CHUNK, "fusion.3", 9, 11), (CHUNK, "fusion.4", 11, 15),
    (CHUNK, "fusion.5", 15, 16), (CHUNK, "fusion.6", 16, 17),
    (CHUNK, "fusion.7", 17, 18), (CHUNK, "fusion.8", 18, 20),
    (FIN, "sort.1", 22, 26), (FIN, "sort.2", 26, 30),
    (FIN, "fusion.3", 30, 38),
    (CHUNK, "sort.1", 52, 58), (CHUNK, "fusion.4", 58, 70),
    (FIN, "sort.2", 75, 90),
    (MG, "fusion.1", 110, 120),
]
SPANS = [
    ("ssumm.make_graph", -20, -10),  # set-up, outside the window
    ("bench.window", 0, 100),
    ("bench.job", 0, 45), ("ssumm.make_graph", 1, 3),
    ("ssumm.engine", 3, 40), ("ssumm.result", 40, 42),
    ("bench.job", 50, 95), ("ssumm.make_graph", 50, 52),
    ("ssumm.engine", 52, 90), ("ssumm.result", 90, 93),
    ("bench.merge_gain", 105, 125),
]
ITERATIONS = (3, 2)  # rounds of the two jobs: 5 in all
WANT = {
    "pair_table_ms": (3 + 6) / 5,
    "summary_metrics_ms": 1 / 5,
    "shingles_ms": 2 / 5,
    "group_tables_ms": (4 + 12) / 5,
    "merge_gain_round_ms": 1 / 5,
    "matching_ms": (1 + 1) / 5,
    "sparsify_ms": (4 + 8 + 15) / 2,
    "make_graph_ms": (2 + 2) / 2,
    # engine 1: idle 3-5, 20-22, 38-40; engine 2: idle 70-75
    "engine_idle_ms": (6 + 5) / 2,
    "result_ms": (2 + 3) / 2,
}


def profile(scopes=SCOPES, spans=SPANS, chips=1) -> trace.Profile:
    ops = [trace.Op(mod, instr, instr.split(".")[0], s * MS, e * MS)
           for mod, instr, s, e in OPS]
    programs = {mod: {i: hlo.Instr({i.split(".")[0]}, scope)
                      for i, scope in table.items()}
                for mod, table in scopes.items()}
    host = [trace.Event(name, s * MS, e * MS) for name, s, e in spans]
    return trace.Profile(chips=[list(ops) for _ in range(chips)],
                         modules=[[] for _ in range(chips)], host=host,
                         programs=programs)


def run_of(prof):
    jobs = [types.SimpleNamespace(result=types.SimpleNamespace(
        iterations_run=n)) for n in ITERATIONS]
    return types.SimpleNamespace(profile=prof, jobs=jobs)


def load_metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name, os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", ALL)
def test_reader(name):
    assert load_metric(name).read(run_of(profile())) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", ALL)
def test_reader_averages_over_chips(name):
    assert load_metric(name).read(run_of(profile(chips=2))) == \
        pytest.approx(WANT[name])


def parent_scopes():
    """The name stacks of a program that opens no scope of its own."""
    strip = {"pair_table", "summary_metrics", "shingles", "group_tables",
             "merge_gain", "matching", "sparsify"}
    return {mod: {i: "/".join(p for p in scope.split("/") if p not in strip)
                  for i, scope in table.items()}
            for mod, table in SCOPES.items()}


@pytest.mark.parametrize("name", ALL)
def test_reader_is_none_on_the_parents_trace(name):
    """No named scope, no ``ssumm.*`` span: nothing to read."""
    bare = profile(scopes=parent_scopes(),
                   spans=[s for s in SPANS if not s[0].startswith("ssumm.")])
    assert load_metric(name).read(run_of(bare)) is None
    # the merge gain's own jit stays in the stack, and is no layer
    assert "jit(merge_gain)" in parent_scopes()[CHUNK]["fusion.5"]


def test_a_layer_without_ops_reads_none():
    scopes = dict(SCOPES, **{CHUNK: dict(SCOPES[CHUNK],
                                         **{"fusion.5": BODY + "mul"})})
    run = run_of(profile(scopes=scopes))
    assert load_metric("merge_gain_round_ms").read(run) is None
    assert load_metric("pair_table_ms").read(run) == \
        pytest.approx(WANT["pair_table_ms"])


def test_no_profile_reads_none():
    run = types.SimpleNamespace(profile=None, jobs=[])
    assert all(load_metric(n).read(run) is None for n in ALL)


@pytest.mark.parametrize("scope, want", [
    (BODY + "pair_table/jit(sort)/sort", "pair_table"),
    (BODY + "matching/pair_table/add", "matching"),
    (BODY + "jit(merge_gain)/merge_gain/mul", "merge_gain"),
    (BODY + "jit(merge_gain)/mul", None),
    ("", None),
])
def test_layer_of(scope, want):
    assert program_layers.layer_of(
        scope, program_layers.ROUND_LAYERS) == want


def test_unattributed_round_time_is_left_out():
    """The round layers cover the chunk's ops but ``fusion.8``."""
    run = run_of(profile())
    secs = program_layers.layer_seconds(run, program_layers.CHUNK,
                                        program_layers.ROUND_LAYERS)
    chunk = sum(e - s for mod, _, s, e in OPS if mod == CHUNK)
    assert 1e3 * sum(secs.values()) == pytest.approx(chunk - 2)


def test_ops_clipped_to_the_window():
    spans = [(n, 6, e) if n == "bench.window" else (n, s, e)
             for n, s, e in SPANS]
    run = run_of(profile(spans=spans))
    # sort.1 of job 1 runs 5-8 ms: 2 of its 3 ms lie in the window
    assert load_metric("pair_table_ms").read(run) == pytest.approx(
        (2 + 6) / 5)
    # the span that began before the window is not the window's
    assert load_metric("make_graph_ms").read(run) == pytest.approx(2 / 2)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The chip trace ``test_trace.py`` reads: a program that named
    nothing. Its one job is given rounds, so that only the missing names
    leave the readers nothing to read."""
    from test_trace import Recorded

    rec = Recorded(tmp_path_factory.mktemp("trace"))
    rec.jobs = run_of(None).jobs[:rec.saved["jobs"]]
    return rec


@pytest.mark.parametrize("name", ALL)
def test_reader_is_none_on_a_recorded_trace_without_names(recorded, name):
    assert recorded.profile.span("bench.window")
    assert load_metric(name).read(recorded) is None
