"""merge_gain_ms: device milliseconds of one call of the program's
merge-gain entry point (``kernels/ops.py::merge_gain``, default backend) on
the operands of a round over the first job's final partition (the timed
shapes), called from the benchmark after the window: the mean run of its
program (``jit_merge_gain``) in the ``bench.merge_gain`` span."""


def read(run):
    profile, mg = getattr(run, "profile", None), getattr(run, "merge_gain",
                                                         None)
    if profile is None or mg is None:
        return None
    runs = profile.module_runs("bench.merge_gain", mg["module"])
    return 1e3 * sum(runs) / len(runs) if runs else None
