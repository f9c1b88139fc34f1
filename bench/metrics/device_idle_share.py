"""1 - (union of the device's operation intervals) / (traced window), from
the profiler trace of the window."""


def read(run):
    if run.device is None:
        return None
    return 100.0 * (1.0 - run.device["busy_s"] / run.device["window_s"])
