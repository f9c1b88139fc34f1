"""The training loop's conversion of each step's metrics to Python floats
(the program's ``train.metrics`` spans ending in the window), per window
step; None where the program records no such span."""


def read(run):
    if run.spans is None or not run.steps or not any(k == "train.metrics" for k, _, _ in run.spans):
        return None
    return 1e3 * sum(run.span_seconds("train.metrics")) / len(run.steps)
