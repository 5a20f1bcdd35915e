"""make_graph_ms: host milliseconds per job of the program's span
``ssumm.make_graph`` in the window (``core/types.py::make_graph``:
canonicalizing the edge list on the host and copying it to the device)."""


def read(run):
    from harness.program_layers import span_ms

    return span_ms(run, "ssumm.make_graph")
