"""Real (not padding) samples rank 0 trained in the window, over its seconds.

A step counts when its ``block_until_ready`` completes inside the window;
the window ends with the completion of its last step."""


def read(run):
    if not run.steps:
        return None
    return sum(real for _, real, _ in run.steps) / run.seconds
