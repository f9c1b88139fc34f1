"""Share of the real samples trained in the window that the plan served from
rank 0's buffer (its hit masks, the counts ``LoaderReport`` sums)."""


def read(run):
    real = sum(r for _, r, _ in run.steps)
    if not real:
        return None
    return 100.0 * sum(h for _, _, h in run.steps) / real
