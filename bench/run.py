#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in ``BENCHMARK.json`` (beside this directory), reads its
configuration file and its traffic file (``bench/traffic/<traffic>.json``),
and hands them to the driver the traffic file names (``bench/harness/``).
The driver sets up from ``--seed``, measures for ``--seconds``, and checks
what the timed path produced against the plain reference. With ``--trace 0``
the result carries the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the profiler and the result carries the cell's per-layer
metrics, each read by its own file ``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: every number compared, with its limit.
The same checks are the last lines of standard error. An earlier line
(``{"event": "window", ...}``) gives the run's counts and the end of each
set-up phase in seconds from the start. Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.

``--rehearse`` runs the cell on the CPU at the small size its configuration
file gives under ``rehearsal``; it prints the checks and the counts, and no
metric.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (BENCH, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class NoChip(RuntimeError):
    pass


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def merge(base: dict, over: dict) -> dict:
    """``base`` with the keys of ``over`` replaced, one level of nesting
    deep (a rehearsal overrides single sizes of a group)."""
    out = dict(base)
    for k, v in over.items():
        out[k] = dict(base[k], **v) if isinstance(v, dict) else v
    return out


def load_cell(name: str, rehearse: bool = False) -> dict:
    """The cell's ``BENCHMARK.json`` entry with its configuration, traffic
    and per-layer metric entries, all found by name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, entry["file"]))
    if rehearse:
        config = merge(config, config.get("rehearsal", {}))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer}


def setup_jax(rehearse: bool):
    """Compile cache in the checkout (or where ``$JAX_COMPILATION_CACHE_DIR``
    says), every program cached, and no source paths in the programs, so
    that the cache's keys do not move with a file's lines."""
    import jax

    if rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        from repro.launch.device import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    return jax


def find_device(jax, chips: int, rehearse: bool) -> dict:
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if rehearse:
        return info
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX sees {info['platform']}")
    if info["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees "
                     f"{info['count']}")
    return dict(info, count=chips)


class CompileCounter:
    """Counts JAX's compile events (tracing, lowering, XLA compilation, a
    compile-cache read) while ``on``."""

    def __init__(self, jax):
        self.on = False
        self.events: dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, name: str, _secs: float, **_kw) -> None:
        if self.on and ("/compile" in name or "compilation_cache" in name):
            self.events[name] = self.events.get(name, 0) + 1

    @property
    def count(self) -> int:
        return sum(self.events.values())


def read_layer_metrics(metrics: list[dict], run) -> dict:
    """Each per-layer metric from its own reader, ``bench/metrics/<name>.py``
    (``read(run) -> float | None``); a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in metrics:
        path = os.path.join(BENCH, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def load_driver(name: str):
    path = os.path.join(BENCH, "harness", name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"no traffic driver {name!r} ({path})")
    return importlib.import_module("harness." + name)


def plain(x: float):
    """A number as JSON has it: a reading that is not finite as a string."""
    return x if math.isfinite(x) else str(x)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, rehearsal sizes, no metrics")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    spec = load_cell(args.workload, args.rehearse)
    jax = setup_jax(args.rehearse)
    phases = {"jax_imported": time.perf_counter() - T_START}
    try:
        device = find_device(jax, spec["cell"]["chips"], args.rehearse)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    phases["device_found"] = time.perf_counter() - T_START
    driver = load_driver(spec["traffic"]["driver"])
    run = driver.Run(spec=spec, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), rehearse=args.rehearse,
                     device=device, t_start=T_START,
                     compiles=CompileCounter(jax), phases=phases)
    run.execute()

    checks = run.checks()  # {name: (value, limit)}
    correct = all(v <= lim for v, lim in checks.values()) and run.complete()
    if args.trace:
        metrics = read_layer_metrics(spec["per_layer"], run)
    else:
        metrics = {m["name"]: {"value": float(run.end_to_end[m["name"]]),
                               "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    print(json.dumps({"event": "window", **run.notes()}), flush=True)
    dev = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    result = {"correct": bool(correct), "attempted": run.attempted,
              "failed": run.failed}
    if args.rehearse:
        result["rehearsal"] = {"device": device,
                               "counts": run.notes(),
                               "metric_names": sorted(metrics)}
    else:
        result["metrics"] = metrics
        if args.trace:
            dev["busy_s"], dev["window_s"] = run.busy_s, run.window_s
            result["device"] = dev
            result["breakdown"] = run.breakdown
        else:
            result["device"] = dev
    result["checks"] = {k: {"value": plain(v), "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} = {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    print(f"check complete = {run.complete()}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
