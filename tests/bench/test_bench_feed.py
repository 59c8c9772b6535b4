"""The open-loop camera feed: due times, handover, and latency from the
due time of an acquisition's last frame, against a fake serial executor."""

import queue
import threading
import time

import numpy as np
import pytest

from bench.cameras import CameraFeed, phases


class _Handle:
    def __init__(self):
        self._done = threading.Event()
        self._out = None

    def set(self, out):
        self._out = out
        self._done.set()

    def result(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError
        return self._out, None


class _SerialExecutor:
    """Serves one acquisition at a time: pulls every group the camera hands
    over, then answers; the acquisition named ``stall`` takes ``stall_s``
    longer, and everything queued behind it waits."""

    def __init__(self, stall: str, stall_s: float):
        self.stall, self.stall_s = stall, stall_s
        self.q = queue.Queue()
        self.thread = threading.Thread(target=self._loop, daemon=True)
        self.thread.start()

    def submit(self, name, source):
        h = _Handle()
        self.q.put((name, source, h))
        return h

    def _loop(self):
        while (item := self.q.get()) is not None:
            name, source, h = item
            groups = list(source)
            if name == self.stall:
                time.sleep(self.stall_s)
            h.set(np.stack(groups).mean(axis=0))

    def close(self):
        self.q.put(None)
        self.thread.join(5)


def _feed(submit, *, interval_us, close_s, cameras=1, seed=3):
    pool = np.arange(4 * 4 * 2 * 2, dtype=np.uint16).reshape(4, 4, 2, 2)
    kept = []
    t0 = time.perf_counter() + 0.05
    feed = CameraFeed(
        submit, pool, cameras=cameras, groups=2, frames_per_group=4,
        frame_interval_us=interval_us, seed=seed, t0=t0, close=t0 + close_s,
        keep=lambda acq, out: kept.append((acq, out)), result_timeout_s=5.0,
    )
    feed.start()
    assert feed.join(timeout=30.0)
    return feed, kept, pool


def test_a_stall_raises_the_latency_of_later_results_not_the_feed():
    ex = _SerialExecutor(stall="c0k2", stall_s=0.3)
    try:
        # 2 groups of 4 frames at 5 ms: one acquisition every 40 ms
        feed, kept, _ = _feed(ex.submit, interval_us=5000.0, close_s=0.6)
    finally:
        ex.close()
    acqs = sorted(feed.acquisitions, key=lambda a: a.index)
    assert len(acqs) >= 12 and all(a.delivered is not None for a in acqs)
    lat = {a.index: a.delivered - a.last_due for a in acqs}
    assert max(lat[k] for k in (0, 1)) < 0.1
    # the stalled one, and those queued behind it, come late
    assert lat[2] > 0.25
    assert lat[3] > 0.2 and lat[4] > 0.15
    # the feed is an open loop: every group went over on time
    lags = [h - d for a in acqs for h, d in zip(a.handed, a.due)]
    assert max(lags) < 0.05
    # due times follow the camera's clock: frame i of acquisition k
    a0, a1 = acqs[0], acqs[1]
    assert a1.due[-1] - a0.due[-1] == pytest.approx(2 * 4 * 5e-3, abs=1e-9)
    assert a0.due[1] - a0.due[0] == pytest.approx(4 * 5e-3, abs=1e-9)


def test_unpaced_cameras_always_have_their_next_group():
    ex = _SerialExecutor(stall="", stall_s=0.0)
    try:
        feed, kept, pool = _feed(ex.submit, interval_us=None, close_s=0.3, cameras=2)
    finally:
        ex.close()
    done = [a for a in feed.acquisitions if a.delivered is not None]
    assert len(done) >= 10
    assert {a.camera for a in done} == {0, 1}
    for acq, out in kept:
        np.testing.assert_array_equal(out, pool[acq.groups].mean(axis=0))
    # a camera's next acquisition starts once its last group is handed over
    by_cam = sorted((a for a in done if a.camera == 0), key=lambda a: a.index)
    for a, b in zip(by_cam, by_cam[1:]):
        assert b.handed[0] >= a.handed[-1]


def test_refused_acquisitions_are_counted_not_served():
    def refuse(name, source):
        raise RuntimeError("full")

    feed, kept, _ = _feed(refuse, interval_us=5000.0, close_s=0.2)
    assert feed.acquisitions and not kept
    assert all(a.refused and a.delivered is None for a in feed.acquisitions)


def test_phases_spread_cameras_over_one_acquisition_whatever_the_seed():
    a = sorted(phases(1, 8, 8, 1.0))
    b = sorted(phases(2**40 + 7, 8, 8, 1.0))
    assert a == b
    assert a[0] > 0 and a[-1] < 8.0
    assert phases(1, 8, 8, 1.0) != phases(2, 8, 8, 1.0)
