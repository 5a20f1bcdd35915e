#!/usr/bin/env python3
"""Readings that the limits of a summarization cell are set from.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--rehearse]

For each seed, in one process: one benchmark run of the cell with a window
of one job (the timed path, ``repro.core.summarize``, and after it the
program's merge gain on the operands of a round over that job's final
partition), and two sets of the numbers that decide ``correct``:

- ``program``: the run's own readings against the float64 references (the
  lower readings, from sound runs);
- ``control``: the references computed in bfloat16 on the device (the
  precision below the float32 the configuration states) and put in the
  program's place: as the reported Eq. (4) size and Eq. (2) RE₁, and as the
  merge gain's answers on the same sampled groups (the upper readings).

Beside them, two witnesses of the merge gain's float32 rounding at the
cell's size (``witness``): the program's kernel run on the host's CPU on the
same operands, and the reference evaluated in float32, on the device and in
numpy; and the operands of the pair with the program's widest Reduction gap
(``worst_pair``).

A state left unchanged by every merge round reads ``supernode_share`` = 1
exactly (every node its own supernode), so that fault needs no run. The
benchmark's own runs never run this. Prints one JSON line per seed, then the
largest program reading and the smallest control reading of each number.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import numpy as np

import run as bench_run


def witnesses(jax, operands, groups, backend) -> dict:
    """The merge-gain readings of the program's kernel on the host's CPU,
    and of the reference in float32 on the device and in numpy."""
    import jax.numpy as jnp
    from harness import merge_gain_check as mgc
    from repro.kernels import ops as kops

    # the kernel scores each group on its own: the sampled groups alone
    picked = tuple(np.asarray(x)[groups] for x in operands[:7]) + operands[7:]
    cpu = jax.devices("cpu")[0]
    rel, red = kops.merge_gain(*(jax.device_put(x, cpu) for x in picked),
                               backend=backend)
    out = {"program_cpu": mgc.readings(np.asarray(rel), np.asarray(red),
                                       picked, np.arange(groups.size))}
    for name, xp in (("reference_f32_device", jnp), ("reference_f32_numpy",
                                                      np)):
        with np.errstate(all="ignore"):
            rel, red = mgc.control(operands, groups, np.float32, xp=xp)
        out[name] = mgc.readings(rel, red, operands, groups)
    return out


def worst_pair(rel, red, operands, groups) -> dict:
    """The operands of the sampled pair whose Reduction lies farthest from
    the float64 reference, as a share of ``t_i + t_j``."""
    from harness import merge_gain_check as mgc

    m, n, s, t, n_u, cidx, w, cbar, log2v = operands
    ref_rel, ref_red, denom = mgc.reference(
        *(np.asarray(x)[groups] for x in (m, n, s, t, n_u, cidx, w)),
        cbar, log2v)
    tg = np.asarray(t, np.float64)[groups]
    scale = np.maximum(tg[:, :, None] + tg[:, None, :], 1.0)
    ok = np.isfinite(ref_rel) & np.isfinite(rel[groups])
    gap = np.where(ok, np.abs(red[groups] - ref_red) / scale, -1.0)
    k, i, j = np.unravel_index(int(np.argmax(gap)), gap.shape)
    g = int(groups[k])
    return {"group": g, "i": int(i), "j": int(j), "gap": float(gap[k, i, j]),
            "n": [float(n[g, i]), float(n[g, j])],
            "t": [float(t[g, i]), float(t[g, j])],
            "s": [float(s[g, i]), float(s[g, j])], "w": float(w[g, i, j]),
            "denom": float(denom[k, i, j]), "red": float(red[g, i, j]),
            "red_ref": float(ref_red[k, i, j]),
            "m_rows_max": [float(m[g, i].max()), float(m[g, j].max())],
            "n_u_max": float(np.asarray(n_u)[g].max()),
            "cbar": float(cbar), "log2v": float(log2v)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    spec = bench_run.load_cell(args.workload, args.rehearse)
    jax = bench_run.setup_jax(args.rehearse)
    try:
        device = bench_run.find_device(jax, spec["cell"]["chips"],
                                       args.rehearse)
    except bench_run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    driver = bench_run.load_driver(spec["traffic"]["driver"])
    import jax.numpy as jnp

    from harness import merge_gain_check, summary_check

    compiles = bench_run.CompileCounter(jax)
    lower, upper = {}, {}
    for seed in seeds:
        run = driver.Run(spec=spec, seed=seed, seconds=0.0, trace=False,
                         rehearse=args.rehearse, device=device,
                         t_start=time.perf_counter(), compiles=compiles)
        run.execute()
        prog = {k: v for k, (v, _) in run.checks().items()}
        res = run.jobs[0].result
        table = summary_check.pair_table(res, run.src, run.dst, run.v)
        want = summary_check.eq2_eq4(table, run.v)
        low = summary_check.eq2_eq4(table, run.v, xp=jnp, dtype=jnp.bfloat16)
        ctrl = summary_check.compare(
            dataclasses.replace(res, size_bits=low["size_bits"],
                                re1=low["re1"]), table, want, run.v,
            run.k_bits)
        operands, groups = run.merge_gain_inputs
        c_rel, c_red = merge_gain_check.control(operands, groups,
                                                jnp.bfloat16)
        ctrl.update(merge_gain_check.readings(c_rel, c_red, operands, groups))
        p_rel, p_red = run.merge_gain_answers
        print(json.dumps({"seed": seed, "iterations": res.iterations_run,
                          "job_s": run.jobs[0].end - run.jobs[0].start,
                          "program": prog, "control": ctrl,
                          "reference": want,
                          "witness": witnesses(jax, operands, groups,
                                               run.merge_gain["backend"]),
                          "worst_pair": worst_pair(p_rel, p_red, operands,
                                                   groups)}), flush=True)
        for k in prog:
            lower[k] = max(lower.get(k, prog[k]), prog[k])
            upper[k] = min(upper.get(k, ctrl[k]), ctrl[k])
    print(json.dumps({"workload": args.workload, "seeds": seeds,
                      "device": device, "largest_program": lower,
                      "smallest_control": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
