"""Multi-host gate: the real launcher on a 2-process × 4-device CPU mesh
must be launcher-JSON bit-identical to the single-process 8-device golden.

    PYTHONPATH=src python tests/multihost_check.py \
        --edge-list data/rmat_1m.txt.gz --T 15 --driver-chunk 1 \
        --rss-budget-mb 3072 --out multihost_report.json

Three legs, each a real ``repro.launch.summarize`` invocation (the harness
never imports jax — every subprocess owns its device topology):

  golden    — 1 process × (P·D) devices, ``--distributed``; also warms
              the CSR cache the multi-host processes feed from.
  multihost — P processes × D devices each, localhost coordinator
              (``jax.distributed``, gloo collectives — DESIGN.md §15).
              Every process's JSON must match the golden bit-for-bit on
              the metric keys, match its peers, report the
              ``cache-mmap-multihost`` feed path, and prove host-local
              staging: ``feed_local_shards == n_dev/P``,
              ``feed_bytes_copied`` exactly 1/P of the total, one staging
              shard high-water mark, and (with ``--rss-budget-mb``)
              per-process peak RSS under budget — no host ever staged a
              full-|E| array.
  resume    — same mesh with ``--checkpoint-dir``; SIGTERM lands on every
              process once a checkpoint commits, all must exit
              RESUMABLE_EXIT (75), and the relaunched ``--resume`` run
              must again match the golden bit-for-bit (PR 7's machinery,
              now with process-0 writes + cross-process preemption
              agreement).

``--bench-out`` writes per-leg wall clocks in the
``scripts/check_bench.py --bench multihost`` artifact format.
"""

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESUMABLE_EXIT = 75  # repro.runtime.RESUMABLE_EXIT (harness is jax-free)

#: launcher JSON keys that must be bit-identical across topologies
EXACT_KEYS = ("V", "E", "mode", "size_bits", "size_bits_before_sparsify",
              "relative_size", "re1", "re2", "num_supernodes",
              "num_superedges", "superedges_dropped")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launcher_cmd(args, extra=()):
    cmd = [sys.executable, "-m", "repro.launch.summarize",
           "--edge-list", args.edge_list,
           "--k-frac", str(args.k_frac), "--T", str(args.T),
           "--seed", str(args.seed), "--group-size", str(args.group_size),
           "--driver-chunk", str(args.driver_chunk), "--distributed"]
    if args.chunk_edges:
        cmd += ["--chunk-edges", str(args.chunk_edges)]
    if args.rss_budget_mb is not None:
        cmd += ["--rss-budget-mb", str(args.rss_budget_mb)]
    return cmd + list(extra)


def env_for(devices):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def last_json(stdout):
    for line in reversed(stdout.strip().splitlines()):
        line = line.strip()
        if line.endswith("}"):
            text = stdout[: stdout.rindex(line) + len(line)]
            start = text.rindex("\n{") if "\n{" in text else text.index("{")
            return json.loads(text[start:])
    raise ValueError(f"no JSON object in stdout:\n{stdout}")


def committed_steps(ckdir):
    if not os.path.isdir(ckdir):
        return []
    return sorted(int(n[len("step_"):]) for n in os.listdir(ckdir)
                  if n.startswith("step_")
                  and os.path.exists(os.path.join(ckdir, n, "COMMIT")))


def compare(got, want, exact):
    bad = []
    for k in exact:
        if k not in want and k not in got:
            continue
        if got.get(k) != want.get(k):
            bad.append(f"{k}: got {got.get(k)!r} want {want.get(k)!r}")
    return bad


class Fleet:
    """P launcher processes sharing one localhost coordinator."""

    def __init__(self, args, extra, workdir, tag):
        port = free_port()
        self.procs, self.outs, self.errs = [], [], []
        for i in range(args.num_processes):
            cmd = launcher_cmd(args, extra=tuple(extra) + (
                "--coordinator", f"localhost:{port}",
                "--num-processes", str(args.num_processes),
                "--process-id", str(i)))
            out = open(os.path.join(workdir, f"{tag}_p{i}.out"), "w+")
            err = open(os.path.join(workdir, f"{tag}_p{i}.err"), "w+")
            self.procs.append(subprocess.Popen(
                cmd, env=env_for(args.devices_per_process),
                stdout=out, stderr=err))
            self.outs.append(out)
            self.errs.append(err)

    def poll_done(self):
        return all(p.poll() is not None for p in self.procs)

    def signal_all(self, sig):
        for p in self.procs:
            if p.poll() is None:
                os.kill(p.pid, sig)

    def wait(self, timeout):
        deadline = time.time() + timeout
        for p in self.procs:
            p.wait(timeout=max(deadline - time.time(), 1.0))

    def finish(self, timeout):
        """Wait, then return (rcs, stdouts, stderrs) and close the files."""
        try:
            self.wait(timeout)
        finally:
            for p in self.procs:
                if p.poll() is None:
                    p.kill()
                    p.wait()
        outs, errs = [], []
        for f in self.outs + self.errs:
            f.flush()
            f.seek(0)
        for f in self.outs:
            outs.append(f.read())
            f.close()
        for f in self.errs:
            errs.append(f.read())
            f.close()
        return [p.returncode for p in self.procs], outs, errs


def main():
    ap = argparse.ArgumentParser(
        description="process-spanning-mesh bit-identity gate")
    ap.add_argument("--edge-list", required=True)
    ap.add_argument("--k-frac", type=float, default=0.3)
    ap.add_argument("--T", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--group-size", type=int, default=32)
    ap.add_argument("--chunk-edges", type=int, default=None)
    ap.add_argument("--driver-chunk", type=int, default=1)
    ap.add_argument("--num-processes", type=int, default=2)
    ap.add_argument("--devices-per-process", type=int, default=4)
    ap.add_argument("--kill-step", type=int, default=2,
                    help="resume leg: SIGTERM the fleet once this "
                         "checkpoint step has committed")
    ap.add_argument("--checkpoint-every", type=int, default=1)
    ap.add_argument("--checkpoint-keep", type=int, default=3)
    ap.add_argument("--rss-budget-mb", type=float, default=None,
                    help="per-process peak-RSS gate for every leg (the "
                         "no-full-|E|-staging proof)")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--out", default=None,
                    help="write the report JSON here (CI artifact)")
    ap.add_argument("--bench-out", default=None,
                    help="write check_bench 'multihost' rows here")
    args = ap.parse_args()
    n_total = args.num_processes * args.devices_per_process

    workdir = args.workdir or os.path.join(
        os.environ.get("TMPDIR", "/tmp"), f"multihost_{os.getpid()}")
    os.makedirs(workdir, exist_ok=True)
    report = {"ok": True, "legs": {}, "errors": []}
    walls = {}

    def fail(msg):
        report["ok"] = False
        report["errors"].append(msg)

    # ---- golden: 1 process x n_total devices (also warms the cache) ------
    t0 = time.time()
    out = subprocess.run(launcher_cmd(args), env=env_for(n_total),
                         capture_output=True, text=True,
                         timeout=args.timeout)
    walls["golden"] = time.time() - t0
    if out.returncode != 0:
        print(out.stderr[-4000:], file=sys.stderr)
        raise SystemExit(f"golden run failed rc={out.returncode}")
    golden = last_json(out.stdout)
    report["legs"]["golden"] = {k: golden.get(k) for k in EXACT_KEYS}
    report["legs"]["golden"]["peak_rss_mb"] = golden.get("peak_rss_mb")

    # ---- multihost: P processes x D devices ------------------------------
    t0 = time.time()
    fleet = Fleet(args, (), workdir, "mh")
    rcs, outs, errs = fleet.finish(args.timeout)
    walls["multihost"] = time.time() - t0
    leg = {"rcs": rcs, "procs": []}
    jsons = []
    for i, (rc, so, se) in enumerate(zip(rcs, outs, errs)):
        if rc != 0:
            fail(f"multihost p{i} rc={rc}: {se[-2000:]}")
            continue
        j = last_json(so)
        jsons.append(j)
        for msg in compare(j, golden, EXACT_KEYS):
            fail(f"multihost p{i} vs golden: {msg}")
        if j.get("feed_path") != "cache-mmap-multihost":
            fail(f"multihost p{i} feed_path={j.get('feed_path')!r}")
        if j.get("process_count") != args.num_processes:
            fail(f"multihost p{i} process_count={j.get('process_count')}")
        # host-local staging proof: this process staged exactly its own
        # 1/P of the shards, one staging buffer high-water mark
        want_shards = n_total // args.num_processes
        if j.get("feed_local_shards") != want_shards:
            fail(f"multihost p{i} feed_local_shards="
                 f"{j.get('feed_local_shards')} != {want_shards}")
        want_copied = want_shards * j["feed_shard_bytes"] * 2  # both columns
        if j.get("feed_bytes_copied") != want_copied:
            fail(f"multihost p{i} feed_bytes_copied="
                 f"{j.get('feed_bytes_copied')} != {want_copied}")
        if j.get("feed_peak_staging_bytes") != j.get("feed_shard_bytes"):
            fail(f"multihost p{i} staged more than one shard: "
                 f"{j.get('feed_peak_staging_bytes')}")
        leg["procs"].append({
            "process_index": j.get("process_index"),
            "peak_rss_mb": j.get("peak_rss_mb"),
            "feed_local_shards": j.get("feed_local_shards"),
            "feed_bytes_copied": j.get("feed_bytes_copied"),
        })
    for j in jsons[1:]:
        for msg in compare(j, jsons[0], EXACT_KEYS):
            fail(f"multihost peers disagree: {msg}")
    report["legs"]["multihost"] = leg

    # ---- resume: SIGTERM the whole fleet, then --resume ------------------
    ckdir = os.path.join(workdir, "ck")
    shutil.rmtree(ckdir, ignore_errors=True)
    ck_extra = ("--checkpoint-dir", ckdir,
                "--checkpoint-every", str(args.checkpoint_every),
                "--checkpoint-keep", str(args.checkpoint_keep))
    t0 = time.time()
    fleet = Fleet(args, ck_extra, workdir, "kill")
    delivered = False
    deadline = time.time() + args.timeout
    while time.time() < deadline and not fleet.poll_done():
        steps = committed_steps(ckdir)
        if steps and steps[-1] >= args.kill_step:
            fleet.signal_all(signal.SIGTERM)
            delivered = True
            break
        time.sleep(0.01)
    rcs, outs, errs = fleet.finish(args.timeout)
    leg = {"delivered": delivered, "kill_rcs": rcs}
    errors_before_kill = len(report["errors"])
    if not delivered:
        # the fleet finished before the kill step committed — compare the
        # completed run directly (kill step too late for this workload)
        leg["outcome"] = "completed"
        for i, (rc, so) in enumerate(zip(rcs, outs)):
            if rc != 0:
                fail(f"resume-leg p{i} completed rc={rc}")
            else:
                for msg in compare(last_json(so), golden, EXACT_KEYS):
                    fail(f"resume-leg completed p{i}: {msg}")
    else:
        for i, (rc, so, se) in enumerate(zip(rcs, outs, errs)):
            if rc != RESUMABLE_EXIT:
                fail(f"resume-leg p{i} SIGTERM rc={rc} != {RESUMABLE_EXIT}"
                     f"\n{se[-2000:]}")
            elif not last_json(so).get("preempted"):
                fail(f"resume-leg p{i} printed no preempted record")
        if not committed_steps(ckdir):
            fail("resume-leg: no committed checkpoint to resume from")
        elif len(report["errors"]) == errors_before_kill:
            leg["resume_from"] = committed_steps(ckdir)[-1]
            fleet = Fleet(args, ck_extra + ("--resume",), workdir, "resume")
            rcs, outs, errs = fleet.finish(args.timeout)
            leg["resume_rcs"] = rcs
            for i, (rc, so, se) in enumerate(zip(rcs, outs, errs)):
                if rc != 0:
                    fail(f"resume p{i} rc={rc}: {se[-2000:]}")
                    continue
                j = last_json(so)
                if j.get("resumed_from") is None:
                    fail(f"resume p{i} did not report resumed_from")
                for msg in compare(j, golden, EXACT_KEYS):
                    fail(f"resume p{i} vs golden: {msg}")
            leg["outcome"] = "resumed"
    walls["resume"] = time.time() - t0
    report["legs"]["resume"] = leg

    report["walls"] = walls
    text = json.dumps(report, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    if args.bench_out:
        rows = [{"bench": "multihost", "leg": leg_name,
                 # golden is the 1-process reference on the same global mesh
                 "processes": (1 if leg_name == "golden"
                               else args.num_processes),
                 "devices_per_process": (n_total if leg_name == "golden"
                                         else args.devices_per_process),
                 "wall_s": wall}
                for leg_name, wall in walls.items()]
        os.makedirs(os.path.dirname(os.path.abspath(args.bench_out)),
                    exist_ok=True)
        with open(args.bench_out, "w") as f:
            json.dump(rows, f, indent=1)
    raise SystemExit(0 if report["ok"] else 1)


if __name__ == "__main__":
    main()
