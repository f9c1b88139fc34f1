"""Time the loader's pipeline thread waited on a step's chunk reads (the
program's ``prefetch.read_wait`` spans ending in the window), per window
step; None where the program records no such span."""


def read(run):
    if run.spans is None or not run.steps or not any(k == "prefetch.read_wait" for k, _, _ in run.spans):
        return None
    return 1e3 * sum(run.span_seconds("prefetch.read_wait")) / len(run.steps)
