"""The result latency's tail: 95th percentile (nearest rank), in ms, of
(result in host memory - due time of the acquisition's last frame) over
every acquisition whose last frame is due in the window. Host stalls of a
tenth of a second, every few seconds, decide it, so it is read here beside
the median rather than bounded end to end."""

from bench.cell import nearest_rank


def read(run):
    if not run.paced:
        return None
    lat = [(a.delivered - a.last_due) * 1e3 for a in run.acquisitions if a.delivered is not None]
    return nearest_rank(lat, 95) if lat else None
