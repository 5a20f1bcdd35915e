"""Shares of the device's busy time by what its ops run, for the per-layer
metric readers. Each op is classed by the ``HloProto`` of the program it ran
in, as the trace itself carries it (``harness/hlo.py``)."""

WINDOW = "bench.window"


def share_of_busy(run, select) -> float | None:
    """Percent of the window's busy device time spent in ops for which
    ``select(instr)`` holds (``instr``: the op's ``hlo.Instr``). Ops the
    trace's programs do not describe count as not selected; their share is
    :func:`unclassified_share`."""
    profile = getattr(run, "profile", None)
    if profile is None or not profile.programs or not run.busy_s:
        return None

    def picked(op) -> bool:
        instr = profile.instr(op)
        return instr is not None and select(instr)

    return 100.0 * profile.op_seconds(WINDOW, picked) / run.busy_s


def unclassified_share(profile, busy_s: float) -> float:
    """Percent of the window's busy device time in ops whose instruction no
    program in the trace describes."""
    if not busy_s:
        return 0.0
    return 100.0 * profile.op_seconds(
        WINDOW, lambda op: profile.instr(op) is None) / busy_s
