"""shingles_ms: device milliseconds per merge round in the window of candidate
grouping (``core/shingles.py::build_groups``: the min-hash shingles and the
sort into groups): the ops of ``jit__local_chunk`` under the program's named
scope ``shingles`` (``harness/program_layers.py``), over Σ
``iterations_run``."""


def read(run):
    from harness.program_layers import round_layer_ms

    return round_layer_ms(run, "shingles")
