"""Arithmetic of the per-layer metrics that read the serve layer's stage
instruments (``serve.h2d_s``, ``serve.backpressure_s``,
``serve.coalesce_s``, ``serve.step_s``, ``serve.finalize_s``) and its
``serve.*`` spans in the profile.

Each registry reader takes the window's delivered acquisitions by their
session names. A program without the instrument gives ``None``, so the
metric is left out of the result line instead of reading 0.
"""

from __future__ import annotations

from bench import xplane
from bench.cell import nearest_rank

__all__ = ["per_group_ms", "p50_ms", "executor_busy_share", "WAIT_SPAN", "IDLE_SPANS"]

#: the executor loop's wait for work; a host line holding it is an executor
WAIT_SPAN = "serve.wait"
#: an executor idles while it waits for work or for a cohort to fill
IDLE_SPANS = (WAIT_SPAN, "serve.coalesce")


def _delivered(run):
    return [a for a in run.acquisitions if a.delivered is not None]


def per_group_ms(run, counter: str):
    """The per-session ``counter`` summed over the window's delivered
    acquisitions, per group, in ms."""
    total, groups = 0.0, 0
    for a in _delivered(run):
        value = run.registry.value(counter, None, session=a.name)
        if value is None:
            continue
        total += value
        groups += len(a.groups)
    return total / groups * 1e3 if groups else None


def p50_ms(run, histogram: str):
    """Nearest-rank median, in ms, of the per-session ``histogram``'s
    samples over the window's delivered acquisitions."""
    names = {a.name for a in _delivered(run)}
    samples = [
        v
        for inst in run.registry.instruments()
        if inst.kind == "histogram" and inst.name == histogram
        and dict(inst.label_key).get("session") in names
        for v in inst._merged()[0]
    ]
    return nearest_rank(samples, 50) * 1e3 if samples else None


def executor_busy_share(data):
    """Serve: % of the ``bench.window`` span in which an executor thread
    was not waiting, mean over the profile's executor threads.

    ``data`` is a ``ProfileData``. An executor thread is a host line that
    holds ``serve.wait`` events; it idles inside ``serve.wait`` and
    ``serve.coalesce`` and is busy everywhere else (admit, dispatch,
    block on the device, finalize, bookkeeping). ``None`` where the
    profile has no window or no executor line.
    """
    window = None
    lines = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            idle, executor = [], False
            for ev in line.events:
                name = ev.name
                if name == xplane.WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif name in IDLE_SPANS:
                    idle.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                    executor = executor or name == WAIT_SPAN
            if executor:
                lines.append(idle)
    if window is None or not lines:
        return None
    w0, w1 = window
    shares = []
    for idle in lines:
        clipped = [(max(s, w0), min(e, w1)) for s, e in idle if min(e, w1) > max(s, w0)]
        waited = sum(e - s for s, e in xplane._union(clipped))
        shares.append(100.0 * (1.0 - waited / (w1 - w0)))
    return sum(shares) / len(shares)
