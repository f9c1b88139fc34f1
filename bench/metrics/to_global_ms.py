"""The program's share of batch assembly: ``StepBatch.to_global`` padding
and stacking the rank's rows into a new array (the program's
``batch.to_global`` spans ending in the window), per window step; None where
the program records no such span."""


def read(run):
    if run.spans is None or not run.steps or not any(k == "batch.to_global" for k, _, _ in run.spans):
        return None
    return 1e3 * sum(run.span_seconds("batch.to_global")) / len(run.steps)
