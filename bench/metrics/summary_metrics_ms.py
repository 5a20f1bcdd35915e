"""summary_metrics_ms: device milliseconds per merge round in the window of the
partition's Eq. (2)/(4) metrics (``core/costs.py::summary_metrics``): the
ops of ``jit__local_chunk`` under the program's named scope
``summary_metrics`` (``harness/program_layers.py``), over Σ
``iterations_run``."""


def read(run):
    from harness.program_layers import round_layer_ms

    return round_layer_ms(run, "summary_metrics")
