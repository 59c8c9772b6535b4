"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, the
device operations that took most time, and idle gaps named by host spans.

The measured window is the host span ``bench.window`` that ``cell.py``
opens at the window's start and closes at its end; every device interval
is clipped to it. Busy time is the union of the intervals of the events on
each chip's ``XLA Ops`` line. An operation is named by the program that
ran it (the ``XLA Modules`` event around it, else its ``hlo_module`` stat)
and by its HLO instruction name, the text before `` = ``. An idle gap is
named after the host span (``serve.*`` from the program, ``bench.*`` from
the benchmark) that overlaps it most, or ``no_span`` where none does.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os

__all__ = ["TraceSummary", "find_xplane", "load", "reduce_trace", "WINDOW_SPAN"]

WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("serve.", "bench.")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class OpTotal:
    """All events of one device operation (one name in one program)."""

    name: str       # the event's name: the HLO instruction, shapes included
    module: str
    count: float    # events, each counted by the share of it inside the window
    seconds: float

    @property
    def short(self) -> str:
        """The HLO instruction's name alone: ``%fusion.2 = f32[...] ...`` ->
        ``fusion.2``."""
        return self.name.split(" = ", 1)[0].strip().lstrip("%")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float               # mean over the chips traced
    busy_by_chip: list[float]
    ops: list[OpTotal]          # summed over chips, most time first
    gaps: list[tuple[str, float]]  # idle gaps, longest first

    def device_ops(self, top: int = 10) -> list[list]:
        by_name: dict[str, float] = {}
        for op in self.ops:
            key = f"{op.module}/{op.short}" if op.module else op.short
            by_name[key] = by_name.get(key, 0.0) + op.seconds
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v] for k, v in ranked]

    def idle_gaps(self, top: int = 10) -> list[list]:
        return [[n, s] for n, s in self.gaps[:top]]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def _modules(plane):
    """Sorted (start, end, name) of the programs run on a device plane."""
    out = []
    for line in plane.lines:
        if line.name == MODULES_LINE:
            for ev in line.events:
                out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name.split("(", 1)[0]))
    out.sort()
    return out


def _module_at(modules, starts, t) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return ""


def load(path: str):
    """The ``ProfileData`` of an ``.xplane.pb`` file, or of an XSpace in
    protobuf text format (``.txt``)."""
    from jax.profiler import ProfileData

    if path.endswith(".txt"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def reduce_trace(data, chips: int) -> TraceSummary:
    """Summarize the trace ``data`` (a ``ProfileData``) over its
    ``bench.window`` span, on the cell's chips ``/device:TPU:0`` to
    ``chips - 1``."""
    spans = []
    window = None
    devices = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            if plane.name[len(DEVICE_PREFIX):] in {str(i) for i in range(chips)}:
                devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(SPAN_PREFIXES) and ev.duration_ns > 0:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    if not devices:
        raise ValueError(f"no {DEVICE_PREFIX}* plane in the trace")
    w0, w1 = window
    spans.sort()
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0)

    totals: dict[tuple[str, str], OpTotal] = {}
    busy_by_chip = []
    gaps = []
    for plane in devices:
        intervals = []
        modules = _modules(plane)
        mod_starts = [m[0] for m in modules]
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s0, e0 = ev.start_ns, ev.start_ns + ev.duration_ns
                s, e = max(s0, w0), min(e0, w1)
                if e <= s:
                    continue
                intervals.append((s, e))
                module = (_module_at(modules, mod_starts, s0)
                          or str(_stats(ev).get("hlo_module", "")))
                key = (ev.name, module)
                t = totals.get(key)
                if t is None:
                    t = totals[key] = OpTotal(ev.name, module, 0.0, 0.0)
                t.count += (e - s) / (e0 - s0)
                t.seconds += (e - s) * 1e-9
        busy = _union(intervals)
        busy_by_chip.append(sum(e - s for s, e in busy) * 1e-9)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                name = _name_gap(g0, g1, spans, starts, longest)
                gaps.append((name, (g1 - g0) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy_by_chip) / len(busy_by_chip),
        busy_by_chip=busy_by_chip,
        ops=sorted(totals.values(), key=lambda t: -t.seconds),
        gaps=gaps,
    )


def _name_gap(g0: int, g1: int, spans, starts, longest) -> str:
    """The host span that overlaps [g0, g1) most; ``no_span`` if none."""
    best, best_ov = "no_span", 0
    for i in range(bisect.bisect_left(starts, g0 - longest), len(spans)):
        s, e, name = spans[i]
        if s >= g1:
            break
        ov = min(e, g1) - max(s, g0)
        if ov > best_ov:
            best, best_ov = name, ov
    return best
