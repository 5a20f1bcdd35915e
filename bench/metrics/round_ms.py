"""round_ms: the engine's host-clock time per merge round over the window's
jobs: Σ ``SummaryResult.chunk_wall_s`` (each chunk ends in a device→host
copy, so it waits for the device) over Σ ``iterations_run``."""


def read(run):
    jobs = getattr(run, "jobs", None)
    if not jobs:
        return None
    walls = sum(sum(j.result.chunk_wall_s) for j in jobs)
    rounds = sum(j.result.iterations_run for j in jobs)
    return 1e3 * walls / rounds if rounds else None
