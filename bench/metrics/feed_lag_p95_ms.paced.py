"""How late the camera feed handed groups over: 95th percentile, in ms,
of (handover - due) over every group of the window's acquisitions. A feed
that runs late starves the system and flatters its latency."""

from bench.cell import nearest_rank


def read(run):
    if not run.paced:
        return None
    lags = [(h - d) * 1e3 for a in run.acquisitions for h, d in zip(a.handed, a.due)]
    return nearest_rank(lags, 95) if lags else None
