"""The tracer's clock, thread CPU time, and the ``obs.clock_sync`` span that
lets a Chrome export be aligned with a profile."""

import time

import pytest

from repro import obs
from repro.obs import trace as obs_trace


class TickClock:
    def __init__(self, start=0.0):
        self.t = start

    def now(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; ``recording`` plays
    the profiler's ``is_enabled``."""

    recording = False
    log: list = []

    def __init__(self, name):
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.recording

    def __enter__(self):
        FakeAnnotation.log.append(("enter", self.name))
        return self

    def set_metadata(self, **kw):
        FakeAnnotation.log.append(("meta", self.name, kw))

    def __exit__(self, *exc):
        FakeAnnotation.log.append(("exit", self.name))
        return False


@pytest.fixture
def fake_bridge(monkeypatch):
    monkeypatch.setattr(obs_trace, "_load_annotation_cls", lambda: FakeAnnotation)
    monkeypatch.setattr(FakeAnnotation, "recording", False)
    monkeypatch.setattr(FakeAnnotation, "log", [])
    return FakeAnnotation


def test_default_clock_is_perf_counter():
    t0 = time.perf_counter()
    now = obs.Tracer().clock.now()
    t1 = time.perf_counter()
    assert t0 <= now <= t1


def test_spans_record_thread_cpu_time_and_export_it_as_tts():
    tr = obs.Tracer(TickClock())
    with tr.span("sleep"):
        time.sleep(0.05)
    with tr.span("spin"):
        t_end = time.perf_counter() + 0.05
        while time.perf_counter() < t_end:
            pass
    sleep, spin = tr.events()
    # a thread that stood still burns no CPU; one that spun burns its span
    assert 0.0 <= sleep["tt1"] - sleep["tt0"] < 0.02
    assert spin["tt1"] - spin["tt0"] >= 0.03
    doc = tr.export_chrome()
    obs.validate_chrome_trace(doc)
    b, e = [ev for ev in doc["traceEvents"] if ev["name"] == "spin"]
    assert (b["ph"], e["ph"]) == ("B", "E")
    assert e["tts"] - b["tts"] == pytest.approx((spin["tt1"] - spin["tt0"]) * 1e6)


def test_clock_sync_waits_for_a_recording_profiler_then_comes_once(fake_bridge):
    tr = obs.Tracer(TickClock(), annotate=True)
    with tr.span("before"):
        pass
    assert tr.names() == ["before"]
    fake_bridge.recording = True
    with tr.span("first", "serve"):
        pass
    with tr.span("second"):
        pass
    assert tr.names() == ["before", "obs.clock_sync", "first", "second"]
    sync = tr.events()[1]
    assert sync["cat"] == "obs" and isinstance(sync["args"]["wall_ns"], int)
    # the sync went through the bridge, ahead of the span that owed it
    entered = [x[1] for x in fake_bridge.log if x[0] == "enter"]
    assert entered == ["before", "obs.clock_sync", "first", "second"]


def test_each_enabling_with_the_bridge_owes_one_clock_sync(fake_bridge):
    tr = obs.get_tracer()
    was = (tr.enabled, tr.clock, tr._annotation_cls, tr._sync_pending)
    fake_bridge.recording = True
    try:
        tr.clear()
        obs.configure(enabled=True, annotate=True, clock=TickClock())
        for _ in range(2):
            with obs.span("a"):
                pass
        obs.configure(enabled=False)
        obs.configure(enabled=True)
        with obs.span("b"):
            pass
        assert tr.names() == ["obs.clock_sync", "a", "a", "obs.clock_sync", "b"]
        # without the bridge nothing is owed
        obs.configure(annotate=False)
        obs.configure(enabled=True)
        with obs.span("c"):
            pass
        assert tr.names()[-1] == "c" and tr.names().count("obs.clock_sync") == 2
    finally:
        tr.enabled, tr.clock, tr._annotation_cls, tr._sync_pending = was
        tr.clear()


def test_span_args_and_thread_cpu_reach_the_profile_at_the_end(fake_bridge):
    tr = obs.Tracer(TickClock(), annotate=True)
    with tr.span("serve.h2d", "serve", session="c0k1", group=3) as sp:
        sp.set(late=True)
    meta = [x for x in fake_bridge.log if x[0] == "meta"]
    assert len(meta) == 1
    _, name, kw = meta[0]
    assert name == "serve.h2d"
    assert {k: kw[k] for k in ("session", "group", "late")} == {
        "session": "c0k1", "group": 3, "late": True
    }
    assert kw["thread_cpu_ms"] >= 0.0
    # metadata is set while the annotation is still open
    assert fake_bridge.log[-2:] == [meta[0], ("exit", "serve.h2d")]


def test_disabled_tracer_with_the_bridge_stays_the_null_span(fake_bridge):
    fake_bridge.recording = True
    tr = obs.Tracer(TickClock(), enabled=False, annotate=True)
    assert tr.span("a") is tr.span("b", session="x")
    with tr.span("a"):
        pass
    assert tr.events() == []
    assert fake_bridge.log == []


def test_profile_names_stay_bare_and_carry_the_span_args(tmp_path):
    """Through the real bridge on a CPU profile: the event is named by the
    span alone, its args (and thread CPU time) are event stats, and the
    clock_sync is there to align on."""
    import jax
    from jax.profiler import ProfileData

    tr = obs.Tracer(annotate=True)
    if tr._annotation_cls is None:
        pytest.skip("jax.profiler.TraceAnnotation is not available")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("serve.dispatch", "serve", session=["c0k1", "c1k0"], size=2, kind="full"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    events = {
        ev.name: dict(ev.stats)
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
    }
    assert "obs.clock_sync" in events
    stats = events["serve.dispatch"]
    assert stats["size"] == 2 and stats["kind"] == "full"
    assert "c1k0" in stats["session"] and "thread_cpu_ms" in stats
    assert not [n for n in events if n.startswith("serve.") and "#" in n]
