"""Read tasks the loader's prefetch executor ran on its I/O pool (the
program's ``prefetch.read_task`` spans ending in the window), per window
step; None where the program records no such span."""

KIND = "prefetch.read_task"


def read(run):
    if run.spans is None or not run.steps or not any(k == KIND for k, _, _ in run.spans):
        return None
    n = sum(1 for k, _, t1 in run.spans if k == KIND and run.t_open < t1 <= run.t_close)
    return n / len(run.steps)
