"""Serve: % of the traced window in which the executor thread was not
waiting for work (``serve.wait``) or for a cohort to fill
(``serve.coalesce``), mean over executor threads, read from the profile
the run left (see bench.stages). A share near 100 means the executor sets
the pace; a low one, that it waits on the acquisition threads."""

from bench import cell, stages, xplane


def read(run):
    if run.trace is None:
        return None
    return stages.executor_busy_share(xplane.load(xplane.find_xplane(str(cell.TRACE_DIR))))
