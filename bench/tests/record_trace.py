#!/usr/bin/env python3
"""Records the small chip trace that ``test_trace.py`` reads.

    python3 bench/tests/record_trace.py   # on a TPU

Runs ``summarize.graph500-s17`` at its rehearsal size under the profiler,
as a traced benchmark run does, and writes into ``bench/tests/data/``:
``small.xplane.pb.gz`` (the trace, which carries the HLO of the programs
that ran) and ``small_run.json`` (the run's shapes, spans and the per-layer
metrics it read).
"""

import gzip
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402


def main() -> int:
    out = os.path.join(HERE, "data")
    os.makedirs(out, exist_ok=True)
    spec = bench_run.load_cell("summarize.graph500-s17", rehearse=True)
    jax = bench_run.setup_jax(False)
    device = bench_run.find_device(jax, 1, False)
    driver = bench_run.load_driver(spec["traffic"]["driver"])
    raw = os.path.join(out, "small.xplane.pb")
    run = driver.Run(spec=spec, seed=7, seconds=0.05, trace=True,
                     rehearse=False, device=device,
                     t_start=time.perf_counter(),
                     compiles=bench_run.CompileCounter(jax),
                     keep_trace_to=raw)
    run.execute()
    with open(raw, "rb") as f, gzip.open(raw + ".gz", "wb") as g:
        shutil.copyfileobj(f, g)
    os.remove(raw)
    metrics = bench_run.read_layer_metrics(spec["per_layer"], run)
    with open(os.path.join(out, "small_run.json"), "w") as f:
        json.dump({"device": device, "merge_gain": run.merge_gain,
                   "busy_s": run.busy_s, "window_s": run.window_s,
                   "jobs": len(run.jobs), "metrics": metrics,
                   "checks": run.checks()}, f, indent=1)
    print(json.dumps(metrics))
    return 0


if __name__ == "__main__":
    sys.exit(main())
