"""Table 2: the dataset registry with realized |V|, |E| and Size(G).

Each row resolves real-data-first (DESIGN.md §10): a SNAP file under
``$SSUMM_DATA_DIR`` → its binary CSR cache → the offline synthetic
stand-in. The ``source`` column labels which one backed the row
(``real|cache|synthetic|spec``). Whenever a graph is actually loaded —
from a real file *or* a stand-in — ``size_g_bits`` is Eq. (3) on the
*realized* |V|, |E|; only never-materialized web-scale rows fall back to
the spec values (``source="spec"``).
"""

from __future__ import annotations

import argparse

from benchmarks.common import emit, save_artifact
from repro.core import costs
from repro.graphs import DATASETS, load_graph
from repro.graphs.io import cache_is_fresh, default_cache_dir, find_real_file


def _resolution(name: str) -> str:
    """Where ``load_graph(name)`` would read from, without loading."""
    path = find_real_file(name)
    if path is not None:
        return "cache" if cache_is_fresh(default_cache_dir(path), path) \
            else "real"
    return "synthetic"


def run(scale=0.05, materialize_max_e=5_000_000) -> list[dict]:
    rows = []
    for name, spec in DATASETS.items():
        row = {"bench": "table2", "name": name, "short": spec.short,
               "V_spec": spec.v, "E_spec": spec.e_target, "kind": spec.kind,
               "size_g_bits_spec":
                   costs.input_size_bits(spec.v, spec.e_target)}
        res = _resolution(name)
        # real files are full-size by definition; synthetic stand-ins only
        # materialize when the scaled |E| fits the budget
        if res != "synthetic" or spec.e_target * scale <= materialize_max_e:
            g = load_graph(name, scale=scale)
            row.update({
                "source": g.source,
                "scale": scale if g.source == "synthetic" else 1.0,
                "V": g.num_nodes, "E": g.num_edges,
                "size_g_bits":
                    costs.input_size_bits(g.num_nodes, g.num_edges),
            })
        else:
            # never materialized, so Eq. (3) on the spec
            # values is all there is — labeled, not silently mixed in
            row.update({"source": "spec", "scale": 0, "V": spec.v,
                        "E": spec.e_target,
                        "size_g_bits":
                            costs.input_size_bits(spec.v, spec.e_target),
                        "note": "spec only"})
        rows.append(row)
        emit(row)
    save_artifact("table2_datasets", rows)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=0.05)
    args = ap.parse_args()
    run(args.scale)


if __name__ == "__main__":
    main()
