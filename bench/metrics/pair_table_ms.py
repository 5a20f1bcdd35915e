"""pair_table_ms: device milliseconds per merge round in the window of the pair
table (``core/costs.py::build_pair_table``: the E-sized sort of the
supernode pairs and their segment sums): the ops of ``jit__local_chunk``
under the program's named scope ``pair_table``
(``harness/program_layers.py``), over Σ ``iterations_run``."""


def read(run):
    from harness.program_layers import round_layer_ms

    return round_layer_ms(run, "pair_table")
