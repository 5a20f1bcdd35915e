"""SSumM driver (Alg. 1): the public entry point of the core library.

``summarize(src, dst, num_nodes, cfg)`` reproduces Alg. 1:

    1. initialize Ḡ := G
    2. while t ≤ T and Size(Ḡ) > k:  candidate generation → merge → sparsify
    3. if Size(Ḡ) > k: further sparsification (drop superedges by ΔRE_p)

plus one guarded extension (``ensure_budget``, DESIGN.md §4): if after T
iterations even the *membership term* |V|log₂|S| exceeds k (so no amount of
edge-dropping can reach the budget), extra θ=0 merge rounds run until the
budget is reachable — this realizes the paper's "always gives a summary
graph whose size does not exceed a given size" claim for very small k.

The loop itself lives in :class:`repro.core.engine.SummaryEngine`
(DESIGN.md §12), driven here through the single-device
:class:`~repro.core.engine.LocalBackend`: the engine dispatches
``cfg.driver_chunk`` jit-compiled rounds per device round-trip
(``lax.while_loop``) and inspects only scalar metrics on chunk boundaries,
matching the paper's per-iteration check (Alg. 1 line 4) without a
device→host sync every round.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from repro.core.engine import LocalBackend, SummaryEngine
from repro.core.types import SummaryConfig, SummaryResult


def summarize(
    src,
    dst,
    num_nodes: int,
    cfg: SummaryConfig = SummaryConfig(),
    collect_history: bool = True,
    *,
    checkpointer=None,
    monitor=None,
    resume: bool = False,
) -> SummaryResult:
    """Run SSumM on an edge list. Returns the summary graph + exact metrics.

    ``checkpointer`` (a :class:`repro.core.engine.EngineCheckpointer`),
    ``monitor`` (a :class:`repro.runtime.straggler.StragglerMonitor`), and
    ``resume`` pass straight through to :meth:`SummaryEngine.run` — the
    crash-safe/preemption-safe path of DESIGN.md §13.

    On a profiler trace one call is three host spans in turn:
    ``ssumm.make_graph``, ``ssumm.engine`` and ``ssumm.result`` (the host
    copies of the summary and the result's assembly).
    """
    backend = LocalBackend(src, dst, num_nodes, cfg)
    run = SummaryEngine(backend).run(collect_history=collect_history,
                                     checkpointer=checkpointer,
                                     monitor=monitor, resume=resume)
    return _result(run)


@functools.partial(jax.profiler.annotate_function, name="ssumm.result")
def _result(run) -> SummaryResult:
    """The summary's host copies, assembled into the result."""
    pt = run.finalize["pair_table"]
    after = run.finalize["after"]
    keep_np = np.asarray(run.finalize["keep"])
    lo = np.asarray(pt.lo)[keep_np]
    hi = np.asarray(pt.hi)[keep_np]
    w = np.asarray(pt.cnt)[keep_np].astype(np.int64)
    return SummaryResult(
        node2super=np.asarray(run.state.node2super),
        super_size=np.asarray(run.state.size),
        edge_lo=lo,
        edge_hi=hi,
        edge_w=w,
        num_supernodes=int(after["num_supernodes"]),
        num_superedges=int(after["num_superedges"]),
        size_bits=float(after["size_bits"]),
        input_size_bits=float(run.input_size_bits),
        re1=float(after["re1"]),
        re2=float(after["re2"]),
        mdl_cost=float(after["mdl_cost"]),
        iterations_run=run.iterations_run,
        history=run.history,
        chunk_wall_s=run.chunk_wall_s,
        straggler_events=run.straggler_events,
        resumed_from=run.resumed_from,
        checkpoint_saves=run.checkpoint_saves,
        checkpoint_snapshot_wall_s=run.checkpoint_snapshot_wall_s,
    )
