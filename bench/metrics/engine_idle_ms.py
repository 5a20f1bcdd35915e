"""engine_idle_ms: device idle milliseconds per job while the program's span
``ssumm.engine`` is open in the window (``SummaryEngine.run``: the chunk
dispatches and their host syncs, the budget check, the finalize): the gaps
between the device's ops inside that span, averaged over the chips."""


def read(run):
    from harness.program_layers import idle_ms_in

    return idle_ms_in(run, "ssumm.engine")
