"""matching_ms: device milliseconds per merge round in the window of matching
and merging (``core/merge.py``: ``select_matching``, ``apply_merges`` and
the sum of the accepted reductions): the ops of ``jit__local_chunk`` under
the program's named scope ``matching`` (``harness/program_layers.py``), over
Σ ``iterations_run``."""


def read(run):
    from harness.program_layers import round_layer_ms

    return round_layer_ms(run, "matching")
