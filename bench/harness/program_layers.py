"""Per-layer times from the names the program gives its own work.

Device layers are the program's ``jax.named_scope``s. They reach each
instruction's ``op_name`` in the ``HloProto`` the trace carries, which
``harness/hlo.py`` reads into ``Instr.scope`` (for a fusion, its root's).
So an op belongs to the first component of that name stack that is one of
the layers asked for: ``jit(_local_chunk)/while/body/pair_table/jit(sort)/
sort`` is ``pair_table``, and a layer nested in another counts for the
outer one. Host phases are the program's ``TraceAnnotation`` spans
(``ssumm.*``), on the trace's clock.

Every reader returns None where the trace holds no op under its layer, or
no such span: the trace of a program that names nothing.
"""

from __future__ import annotations

from harness import trace
from harness.layers import WINDOW

CHUNK = "jit__local_chunk"
FINALIZE = "jit__local_finalize"
#: The merge round's layers, each a ``jax.named_scope`` of the program.
ROUND_LAYERS = ("pair_table", "summary_metrics", "shingles", "group_tables",
                "merge_gain", "matching")
SPARSIFY = "sparsify"


def layer_of(scope: str, layers) -> str | None:
    """The first component of the name stack ``scope`` in ``layers``."""
    return next((part for part in scope.split("/") if part in layers), None)


def jobs(run) -> list:
    return getattr(run, "jobs", None) or []


def layer_seconds(run, program: str, layers) -> dict:
    """``{layer: device seconds}``, averaged over the chips, of the ops of
    ``program`` inside the window, each counted for its layer; ops in no
    layer are left out."""
    profile = getattr(run, "profile", None)
    if profile is None:
        return {}
    lo, hi = profile.span(WINDOW)
    memo: dict = {}
    totals: dict = {}
    for ops in profile.chips:
        for op in ops:
            if op.end <= lo or op.start >= hi \
                    or trace.program_name(op.module) != program:
                continue
            key = (op.module, op.instr)
            if key not in memo:
                instr = profile.instr(op)
                memo[key] = layer_of(instr.scope, layers) if instr else None
            if memo[key] is not None:
                totals[memo[key]] = totals.get(memo[key], 0) + (
                    min(op.end, hi) - max(op.start, lo))
    chips = max(1, len(profile.chips))
    return {k: v * 1e-9 / chips for k, v in totals.items()}


def round_layer_ms(run, layer: str) -> float | None:
    """Device ms per merge round of ``layer`` in the window: its ops in the
    round program over Σ ``iterations_run`` of the window's jobs."""
    secs = layer_seconds(run, CHUNK, ROUND_LAYERS)
    rounds = sum(j.result.iterations_run for j in jobs(run))
    if layer not in secs or not rounds:
        return None
    return 1e3 * secs[layer] / rounds


def sparsify_ms(run) -> float | None:
    """Device ms per job of the finalize's ops under ``sparsify``."""
    secs = layer_seconds(run, FINALIZE, (SPARSIFY,))
    if SPARSIFY not in secs or not jobs(run):
        return None
    return 1e3 * secs[SPARSIFY] / len(jobs(run))


def spans_in_window(run, name: str) -> list:
    """The host spans called ``name`` that lie inside the window."""
    profile = getattr(run, "profile", None)
    if profile is None:
        return []
    lo, hi = profile.span(WINDOW)
    return [e for e in profile.host
            if e.name == name and e.start >= lo and e.end <= hi]


def span_ms(run, name: str) -> float | None:
    """Host ms per job of the spans called ``name`` in the window."""
    spans = spans_in_window(run, name)
    if not spans or not jobs(run):
        return None
    return 1e-6 * sum(e.end - e.start for e in spans) / len(jobs(run))


def idle_ms_in(run, name: str) -> float | None:
    """Device idle ms per job while a span called ``name`` is open in the
    window, averaged over the chips."""
    spans = spans_in_window(run, name)
    chips = run.profile.chips if spans else []
    if not chips or not jobs(run):
        return None
    idle = sum(e - s for ops in chips for span in spans
               for s, e in trace.gaps([(o.start, o.end) for o in ops
                                       if o.end > span.start
                                       and o.start < span.end],
                                      span.start, span.end))
    return 1e-6 * idle / len(chips) / len(jobs(run))
