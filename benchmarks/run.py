"""Benchmark orchestrator: one suite per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # standard suite
    PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized
    PYTHONPATH=src python -m benchmarks.run --only fig4 fig6

Emits ``key=value`` CSV rows (stdout) and JSON artifacts under
``artifacts/bench/``. Sized for the 1-core CPU container; every suite
accepts larger settings via its own __main__ for real runs.
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", nargs="+", default=None,
                    help="subset: table2 fig4 fig5 fig6 fig8 fidelity kernels")
    args = ap.parse_args()

    quick = args.quick
    suites = args.only or ["table2", "fig4", "fig5", "fig6", "fig8",
                           "fidelity", "kernels"]
    t_start = time.time()

    if "table2" in suites:
        from benchmarks import table2_datasets
        print("# --- Table 2: datasets -------------------------------------")
        table2_datasets.run(scale=0.02 if quick else 0.05)

    # NOTE on full-mode sizes: k-Gs/SAA-Gs are O(|V|²·deg) sequential
    # baselines (the paper's own scalability point) — multi-method figures
    # therefore run on small-|V| graphs; SSumM-only figures use larger ones.
    if "fig4" in suites:
        from benchmarks import fig4_compactness
        print("# --- Fig. 4/7: compactness & accuracy ----------------------")
        fig4_compactness.run(
            datasets=("ego-facebook",) if quick else ("ego-facebook",),
            scale=0.1 if quick else 0.25,
            fracs=(0.2, 0.4) if quick else (0.1, 0.2, 0.3, 0.4, 0.5, 0.6),
            methods=("ssumm", "kgs", "s2l", "saa_gs"),
        )
        if not quick:  # second dataset at baseline-feasible |V|
            fig4_compactness.run(
                datasets=("dblp",), scale=0.01,
                fracs=(0.2, 0.4, 0.6),
                methods=("ssumm", "kgs", "s2l", "saa_gs"),
            )

    if "fig5" in suites:
        from benchmarks import fig5_speed
        print("# --- Fig. 5: speed vs quality ------------------------------")
        fig5_speed.run(
            datasets=("ego-facebook",) if quick else ("ego-facebook",),
            scale=0.1 if quick else 0.25,
        )
        if not quick:
            fig5_speed.run(datasets=("dblp",), scale=0.01)

    if "fig6" in suites:
        from benchmarks import fig6_scalability
        print("# --- Fig. 6: scalability -----------------------------------")
        fig6_scalability.run(
            scales=(0.005, 0.01, 0.02) if quick else (0.01, 0.02, 0.04, 0.08),
            T=3 if quick else 5,
        )

    if "fig8" in suites:
        from benchmarks import fig8_iterations
        print("# --- Fig. 8: iterations ------------------------------------")
        fig8_iterations.run(
            scale=0.01 if quick else 0.02,
            targets=(0.3, 0.8) if quick else (0.3, 0.5, 0.8),
        )

    if "fidelity" in suites:
        from benchmarks import fidelity
        print("# --- fidelity: vectorized vs sequential oracle --------------")
        # the oracle is the O(small-graph) sequential Alg. 1/2 — sizes are
        # capped accordingly (same rationale as fig4)
        fidelity.run(
            datasets=("ego-facebook",) if quick else ("ego-facebook",),
            scale=0.05 if quick else 0.1,
            k_fracs=(0.3,) if quick else (0.3, 0.5),
            T=10 if quick else 20,
        )
        if not quick:
            fidelity.run(datasets=("dblp",), scale=0.01, k_fracs=(0.3,), T=20)

    if "kernels" in suites:
        from benchmarks import kernelbench
        print("# --- kernels: merge-gain throughput ------------------------")
        kernelbench.run(sizes=((64, 32, 128),) if quick
                        else ((256, 32, 128), (64, 64, 256)))

    print(f"# total bench wall: {time.time()-t_start:.1f}s")


if __name__ == "__main__":
    main()
