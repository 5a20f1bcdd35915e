"""device_idle_share.summarize: percent of the summarization window in which
no op ran on the device (1 − the union of the op intervals over the
window), averaged over the chips."""


def read(run):
    if getattr(run, "profile", None) is None or not run.window_s:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
