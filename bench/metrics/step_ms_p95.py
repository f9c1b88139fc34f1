"""95th percentile of the intervals between successive step completions in
the window, the first measured from the window's opening: rank 0's straggler
tail, which in a synchronous job sets every rank's pace."""
import statistics


def read(run):
    done = [t for t, _, _ in run.steps]
    if len(done) < 2:
        return None
    gaps = [b - a for a, b in zip([run.t_open] + done, done)]
    return 1e3 * statistics.quantiles(gaps, n=100, method="inclusive")[94]
