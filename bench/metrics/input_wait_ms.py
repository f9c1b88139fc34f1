"""Time the training loop waited for its next batch (the program's
``prefetch.qwait`` spans ending in the window), per window step."""


def read(run):
    if run.spans is None or not run.steps:
        return None
    return 1e3 * sum(run.span_seconds("prefetch.qwait")) / len(run.steps)
