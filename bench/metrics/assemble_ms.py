"""Host batch assembly (the program's ``train.make_batch`` spans ending in
the window: ``StepBatch.to_global``, the input/target split and
``device_put``), per window step."""


def read(run):
    if run.spans is None or not run.steps:
        return None
    return 1e3 * sum(run.span_seconds("train.make_batch")) / len(run.steps)
