"""Physical reads the PFS stand-in charged that completed in the window, per
window step."""


def read(run):
    if not run.steps:
        return None
    n = sum(1 for _, t1, _ in run.pfs_reads if run.t_open < t1 <= run.t_close)
    return n / len(run.steps)
