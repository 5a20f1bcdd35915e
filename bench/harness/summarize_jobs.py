"""Driver of the ``summarize_jobs`` traffic: summarization jobs back to back.

Set-up generates the configuration's graph from the seed and warms the
backend's programs by calling its primitives once (one merge round, one
finalize), not by running a whole job. The window then runs whole jobs,
each ``repro.core.summarize`` from the host edge list to a
``SummaryResult`` on the host: new jobs start until ``seconds`` have passed,
and the job in flight finishes. After the window every job's summary is
checked against the float64 numpy reference (``summary_check``), and the
program's merge-gain entry point, called on the operands of a round over the
first job's final partition, against the float64 merge-gain reference
(``merge_gain_check``).

In a traced run the profiler records the window, then that merge-gain call
repeated, so the kernel's own time can be read apart from the fused round.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import tempfile
import time

import numpy as np

from harness import graphs, merge_gain_check, summary_check, trace
from harness.layers import unclassified_share
from harness.roofline import peaks

#: Timed merge-gain calls in a traced run.
MERGE_GAIN_CALLS = 20
#: Share of the window's busy device time, in percent, that may run in ops
#: no program in the trace describes before a traced run is refused.
UNCLASSIFIED_LIMIT = 2.0


class TraceUnreadable(RuntimeError):
    pass


@dataclasses.dataclass
class Job:
    start: float
    end: float
    result: object


@dataclasses.dataclass
class Run:
    spec: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    device: dict
    t_start: float
    compiles: object
    phases: dict = dataclasses.field(default_factory=dict)
    jobs: list = dataclasses.field(default_factory=list)
    readings: list = dataclasses.field(default_factory=list)
    end_to_end: dict = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int = 0
    setup_s: float = 0.0
    window_s: float = 0.0
    busy_s: float = 0.0
    breakdown: dict | None = None
    profile: object = None  # trace.Profile of a traced run
    merge_gain: dict | None = None
    merge_gain_readings: dict = dataclasses.field(default_factory=dict)
    merge_gain_inputs: tuple | None = None  # (operands, sampled groups)
    merge_gain_answers: tuple | None = None  # the program's (rel, red)
    unclassified_pct: float | None = None
    keep_trace_to: str | None = None  # copy the trace file here (tests)

    # ------------------------------------------------------------ set-up
    def summary_config(self):
        from repro.core import SummaryConfig

        s = self.spec["config"]["summary"]
        return SummaryConfig(
            T=s["T"], k_frac=s["k_frac"], group_size=s["group_size"],
            max_neighbors=s["max_neighbors"], union_size=s["union_size"],
            kernel_backend=s["kernel_backend"],
            driver_chunk=s["driver_chunk"], seed=self.seed % (2 ** 31))

    def warm_up(self, backend) -> None:
        """Every program a job runs, once: a one-round chunk, the budget
        check, the finalize and the host copies of its outputs."""
        import jax.numpy as jnp

        cfg = backend.cfg
        k_bits = cfg.target_bits(backend.input_size_bits())
        state = backend.init()
        thetas = jnp.asarray(np.zeros(max(1, cfg.driver_chunk), np.float32))
        state, buf, rounds = backend.run_chunk(state, thetas, 1, k_bits, 1)
        int(rounds)
        {k: np.asarray(v) for k, v in buf.items()}
        backend.num_supernodes(state)
        fin = backend.sparsify_finalize(state, k_bits, 2)
        pt = fin["pair_table"]
        for x in (fin["keep"], pt.lo, pt.hi, pt.cnt):
            np.asarray(x)
        {k: float(v) for k, v in fin["after"].items() if np.ndim(v) == 0}

    def mark(self, phase: str) -> None:
        """Seconds from the harness's start to the end of a set-up phase."""
        self.phases[phase] = time.perf_counter() - self.t_start

    def merge_gain_operands(self, backend, res):
        """The merge-gain operands of a round over ``res``'s partition, from
        the program's own ``merge.scoring_operands``, at the timed shapes."""
        import jax
        import jax.numpy as jnp

        from repro.core import merge
        from repro.core.types import SummaryState

        cfg = backend.cfg
        fn = jax.jit(merge.scoring_operands, static_argnames=("cfg",))
        init = backend.init()
        state = SummaryState(
            node2super=jnp.asarray(res.node2super, init.node2super.dtype),
            size=jnp.asarray(res.super_size, init.size.dtype),
            rng=init.rng, t=init.t)
        _, k_groups = jax.random.split(state.rng)
        gt, metrics = fn(backend.graph.src, backend.graph.dst, state,
                         cfg=cfg, k_groups=k_groups)
        args = (gt.m, gt.n, gt.s, gt.t, gt.n_u, gt.cidx, gt.w,
                metrics["cbar"], jnp.log2(jnp.float32(backend.num_nodes)))
        return jax.block_until_ready(args)

    # ------------------------------------------------------------ the run
    def execute(self) -> None:
        import jax

        from repro.core import summarize
        from repro.core.engine import LocalBackend

        self.mark("program_imported")
        self.src, self.dst, self.v = graphs.generate(self.spec["config"],
                                                     self.seed)
        self.mark("graph")
        cfg = self.summary_config()
        backend = LocalBackend(self.src, self.dst, self.v, cfg)
        self.mark("edges_on_device")
        self.warm_up(backend)
        self.mark("warm_up")
        self.k_bits = cfg.target_bits(
            summary_check.size_bits_of_graph(self.v, self.src.size))
        if self.trace:
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(tdir)
            self.mark("profiler")

        t0 = time.perf_counter()
        self.setup_s = t0 - self.t_start
        self.compiles.on = True
        with jax.profiler.TraceAnnotation("bench.window"):
            while not self.jobs or time.perf_counter() - t0 < self.seconds:
                with jax.profiler.TraceAnnotation("bench.job"):
                    start = time.perf_counter()
                    res = summarize(self.src, self.dst, self.v, cfg,
                                    collect_history=False)
                    self.jobs.append(Job(start, time.perf_counter(), res))
        self.compiles.on = False
        self.window_s = self.jobs[-1].end - t0
        stats = jax.devices()[0].memory_stats() or {}
        self.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))

        self.check_merge_gain(backend)
        if self.trace:
            jax.profiler.stop_trace()
            files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                              recursive=True)
            self.profile = trace.Profile.load(files[0])
            if self.keep_trace_to:
                shutil.copyfile(files[0], self.keep_trace_to)
            shutil.rmtree(tdir, ignore_errors=True)
            self.busy_s, self.window_s = self.profile.busy_window(
                "bench.window")
            self.unclassified_pct = unclassified_share(self.profile,
                                                       self.busy_s)
            if self.unclassified_pct > UNCLASSIFIED_LIMIT:
                raise TraceUnreadable(
                    f"{self.unclassified_pct:.3f}% of the window's device "
                    f"time ran in ops no program in the trace describes "
                    f"(limit {UNCLASSIFIED_LIMIT}%)")
            self.breakdown = self.profile.breakdown("bench.window",
                                                    label=self.op_label)

        self.check_jobs()
        e = float(self.src.size)
        self.end_to_end = {
            "setup_s": self.setup_s,
            "summarize_edges_per_s": e * len(self.jobs) / (
                self.jobs[-1].end - self.jobs[0].start),
            "summary_re1": float(np.mean([r["re1"] for r in self.readings])),
            "peak_hbm_gb": self.memory_peak_bytes / 1e9,
        }

    def check_merge_gain(self, backend) -> None:
        """The program's merge gain on the operands of a round over the
        first job's final partition, against the float64 reference on a
        sample of groups drawn from the seed; in a traced run, that call
        timed ``MERGE_GAIN_CALLS`` times in the span ``bench.merge_gain``."""
        import jax

        from repro.kernels import ops as kops

        args = self.merge_gain_operands(backend, self.jobs[0].result)
        kernel = kops.resolve_kernel_backend(backend.cfg.kernel_backend)
        rel, red = jax.block_until_ready(kops.merge_gain(*args,
                                                         backend=kernel))
        if self.trace:
            with jax.profiler.TraceAnnotation("bench.merge_gain"):
                for _ in range(MERGE_GAIN_CALLS):
                    jax.block_until_ready(kops.merge_gain(*args,
                                                          backend=kernel))
        g, c, u = (int(x) for x in args[0].shape)
        self.merge_gain = {"calls": MERGE_GAIN_CALLS if self.trace else 0,
                           "shape": (g, c, u), "module": "jit_merge_gain",
                           "backend": kernel}
        operands = tuple(np.asarray(x) for x in args)
        rel, red = np.asarray(rel), np.asarray(red)
        del args
        groups = merge_gain_check.sample_groups(operands[1], self.seed)
        self.merge_gain_readings = merge_gain_check.readings(
            rel, red, operands, groups)
        self.merge_gain["sampled_groups"] = int(groups.size)
        self.merge_gain_inputs = (operands, groups)
        self.merge_gain_answers = (rel, red)

    #: What an op in the breakdown is tagged with: those of these that it
    #: runs (its own opcode or ones it fuses).
    LABELS = ("sort", "scatter", "gather", "reduce", "dynamic-slice",
              "dynamic-update-slice", "log")

    def op_label(self, op) -> str:
        instr = self.profile.instr(op)
        ops = instr.ops if instr else {op.opcode}
        hit = [k for k in self.LABELS if k in ops]
        if instr and "jit(merge_gain)" in instr.scope:
            hit.append("merge_gain")
        return f" ({'+'.join(hit)})" if hit else ""

    def check_jobs(self) -> None:
        self.per_job = []
        for job in self.jobs:
            table = summary_check.pair_table(job.result, self.src, self.dst,
                                             self.v)
            want = summary_check.eq2_eq4(table, self.v)
            self.readings.append(want)
            self.per_job.append(summary_check.compare(
                job.result, table, want, self.v, self.k_bits))

    # ------------------------------------------------------------ results
    @property
    def attempted(self) -> int:
        return len(self.jobs)

    @property
    def failed(self) -> int:
        """Jobs whose summary breaks a limit."""
        limits = self.limits()
        return sum(any(r[k] > limits[k] for k in r if k in limits)
                   for r in self.per_job)

    def limits(self) -> dict:
        return self.spec["config"]["limits"]

    def checks(self) -> dict:
        """``{number: (reading, limit)}``: the worst job's summary numbers,
        then the merge gain's."""
        got = dict(summary_check.worst(self.per_job),
                   **self.merge_gain_readings)
        return {k: (got[k], lim) for k, lim in self.limits().items()}

    def complete(self) -> bool:
        return (bool(self.jobs) and len(self.per_job) == len(self.jobs)
                and bool(self.merge_gain_readings))

    def notes(self) -> dict:
        return {"jobs": len(self.jobs), "edges": int(self.src.size),
                "nodes": self.v, "window_s": self.window_s,
                "setup_s": self.setup_s,
                "iterations": [j.result.iterations_run for j in self.jobs],
                "compiles_in_window": self.compiles.count,
                "compile_events": self.compiles.events,
                "merge_gain": self.merge_gain,
                "unclassified_pct": self.unclassified_pct,
                "setup_phases_s": self.phases}

    def peaks(self) -> dict:
        return peaks(self.device["kind"])
