"""group_tables_ms: device milliseconds per merge round in the window of the
groups' merge-gain operands (``core/tables.py::build_group_tables``:
neighbour tables, union space, the [G, C, C] pair counts): the ops of
``jit__local_chunk`` under the program's named scope ``group_tables``
(``harness/program_layers.py``), over Σ ``iterations_run``."""


def read(run):
    from harness.program_layers import round_layer_ms

    return round_layer_ms(run, "group_tables")
