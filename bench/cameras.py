"""The open-loop camera feed: one general generator that every traffic mix
parameterizes.

A camera runs PRISM acquisitions back to back; each acquisition is one
``Session`` of G groups of N frames. Paced (``frame_interval_us`` set),
frame i of acquisition k of camera c is due at
``t0 + phase_c + (k*G*N + i) * interval``; a group is handed over once its
last frame is due, and the feed never waits for the system: it is an open
loop. Unpaced (``frame_interval_us`` null), every camera always has its
next group ready: a group is handed over as soon as the session pulls it,
and the camera's next acquisition starts when the last group of the
current one is handed over.

Phases spread the cameras evenly, the camera order drawn from the seed, so
every seed gives the same arrivals: camera slot p of S starts
``(p + 1/2)/S`` of a group interval into group ``floor(p*G/S)`` of its
first acquisition. New acquisitions start until the feed closes; those
already started run to their end.

A result is delivered when its output is in host memory (``np.asarray`` of
``handle.result()``), in one collector thread per camera. Its latency runs
from the due time of the acquisition's last frame (unpaced: the handover
of its last group) to that moment.
"""

from __future__ import annotations

import dataclasses
import heapq
import queue
import threading
import time
from typing import Any, Callable

import numpy as np

from bench.pool import acquisition_groups

__all__ = ["Acquisition", "CameraFeed", "phases"]


@dataclasses.dataclass
class Acquisition:
    camera: int
    index: int
    groups: np.ndarray            # pool indices, one per group
    due: list[float]              # due time of each group's last frame
    handed: list[float]           # when each group was handed over
    source_wait_s: float = 0.0    # time the session's pull waited on the camera
    handle: Any = None
    refused: str | None = None
    error: str | None = None
    delivered: float | None = None
    output: np.ndarray | None = None

    @property
    def name(self) -> str:
        return f"c{self.camera}k{self.index}"

    @property
    def last_due(self) -> float | None:
        return self.due[-1] if len(self.due) == len(self.groups) else None


def phases(seed: int, cameras: int, groups: int, group_s: float) -> list[float]:
    """Start offset of each camera's first acquisition (see module doc)."""
    order = np.random.default_rng((seed, 0x5EED)).permutation(cameras)
    return [
        ((int(p) + 0.5) / cameras + (int(p) * groups) // cameras) * group_s
        for p in order
    ]


class CameraFeed:
    """Feeds ``cameras`` cameras into ``submit`` from ``t0`` until ``close``.

    ``submit(name, source)`` seats one acquisition's session and returns
    its handle; ``source`` yields the acquisition's G groups.
    ``keep(acq, output)``
    stores a delivered output when it is to be kept for the correctness check.
    """

    def __init__(
        self,
        submit: Callable[[str, Any], Any],
        pool: np.ndarray,
        *,
        cameras: int,
        groups: int,
        frames_per_group: int,
        frame_interval_us: float | None,
        seed: int,
        t0: float,
        close: float,
        keep: Callable[[Acquisition, np.ndarray], None],
        result_timeout_s: float,
    ):
        self.submit = submit
        self.pool = pool
        self.cameras = cameras
        self.groups = groups
        self.n = frames_per_group
        self.interval = None if frame_interval_us is None else frame_interval_us * 1e-6
        self.seed = seed
        self.t0 = t0
        self.close = close
        self.keep = keep
        self.result_timeout_s = result_timeout_s
        self.acquisitions: list[Acquisition] = []
        self._lock = threading.Lock()
        self._next_index = [0] * cameras
        self._done_q = [queue.Queue() for _ in range(cameras)]  # -> collectors
        self._ready_q: queue.Queue = queue.Queue()  # unpaced: camera wants its next
        self._threads = [threading.Thread(target=self._feed, name="bench-feed", daemon=True)]
        self._threads += [
            threading.Thread(target=self._collect, args=(c,), name=f"bench-collect{c}",
                             daemon=True)
            for c in range(cameras)
        ]

    # -- life cycle -----------------------------------------------------------
    def start(self) -> None:
        for t in self._threads:
            t.start()

    def join(self, timeout: float) -> bool:
        """Wait for the feed and every collector; ``False`` if one is still
        running at ``timeout`` seconds."""
        end = time.perf_counter() + timeout
        for t in self._threads:
            t.join(max(0.0, end - time.perf_counter()))
        return not any(t.is_alive() for t in self._threads)

    # -- feeding ----------------------------------------------------------------
    def _new(self, camera: int) -> Acquisition:
        k = self._next_index[camera]
        self._next_index[camera] += 1
        acq = Acquisition(
            camera, k, acquisition_groups(self.seed, camera, k, self.groups, len(self.pool)),
            due=[], handed=[],
        )
        with self._lock:
            self.acquisitions.append(acq)
        return acq

    def _start(self, acq: Acquisition, source) -> None:
        try:
            acq.handle = self.submit(acq.name, source)
        except Exception as e:  # refused by admission control
            acq.refused = f"{type(e).__name__}: {e}"
        self._done_q[acq.camera].put(acq)

    def _feed(self) -> None:
        try:
            if self.interval is None:
                self._feed_unpaced()
            else:
                self._feed_paced()
        finally:
            for q in self._done_q:
                q.put(None)

    def _feed_paced(self) -> None:
        dt, g, n = self.interval, self.groups, self.n
        acq_s = g * n * dt
        events: list = []  # (time, seq, camera, acquisition | None, group, queue)
        seq = 0
        for c, phase in enumerate(phases(self.seed, self.cameras, g, n * dt)):
            heapq.heappush(events, (self.t0 + phase, seq, c, None, 0, None))
            seq += 1
        while events:
            t, _, c, acq, j, q = heapq.heappop(events)
            wait = t - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            if acq is None:  # camera c starts an acquisition at t
                if t >= self.close:
                    continue
                acq = self._new(c)
                q = queue.Queue()
                self._start(acq, self._paced_source(acq, q))
                for jj in range(g):
                    due = t + ((jj + 1) * n - 1) * dt
                    heapq.heappush(events, (due, seq, c, acq, jj, q))
                    seq += 1
                heapq.heappush(events, (t + acq_s, seq, c, None, 0, None))
                seq += 1
                continue
            acq.due.append(t)
            acq.handed.append(time.perf_counter())
            q.put(self.pool[acq.groups[j]])

    def _paced_source(self, acq: Acquisition, q: queue.Queue):
        for _ in range(self.groups):
            t = time.perf_counter()
            group = q.get()
            acq.source_wait_s += time.perf_counter() - t
            yield group

    def _feed_unpaced(self) -> None:
        for c in range(self.cameras):
            self._ready_q.put(c)
        live = self.cameras
        while live:
            c = self._ready_q.get()
            if time.perf_counter() >= self.close:
                live -= 1
                continue
            acq = self._new(c)
            self._start(acq, self._unpaced_source(acq))
            if acq.refused is not None:
                time.sleep(0.001)
                self._ready_q.put(c)

    def _unpaced_source(self, acq: Acquisition):
        for j, idx in enumerate(acq.groups):
            now = time.perf_counter()
            acq.due.append(now)
            acq.handed.append(now)
            if j == len(acq.groups) - 1:
                self._ready_q.put(acq.camera)
            yield self.pool[idx]

    # -- collecting -------------------------------------------------------------
    def _collect(self, camera: int) -> None:
        q = self._done_q[camera]
        while (acq := q.get()) is not None:
            if acq.handle is None:
                continue
            left = self.close + self.result_timeout_s - time.perf_counter()
            try:
                out, _ = acq.handle.result(timeout=max(left, 0.0))
                host = np.asarray(out)
            except TimeoutError:
                continue  # never came: missing
            except Exception as e:
                acq.error = f"{type(e).__name__}: {e}"
                continue
            acq.delivered = time.perf_counter()
            # the output is in host memory: free its device copy, as a
            # client that drops the handle would
            if hasattr(out, "delete"):
                out.delete()
            acq.handle = None
            self.keep(acq, host)
