"""merge_gain_round_ms: device milliseconds per merge round in the window of
the merge gain inside the rounds (``kernels/ops.py::merge_gain``, the
backend the configuration names): the ops of ``jit__local_chunk`` under the
program's named scope ``merge_gain`` (``harness/program_layers.py``), over Σ
``iterations_run``."""


def read(run):
    from harness.program_layers import round_layer_ms

    return round_layer_ms(run, "merge_gain")
