"""result_ms: host milliseconds per job of the program's span
``ssumm.result`` in the window (``core/summarize.py``: host copies of the
pair table and the ``keep`` mask, and the ``SummaryResult``'s assembly)."""


def read(run):
    from harness.program_layers import span_ms

    return span_ms(run, "ssumm.result")
