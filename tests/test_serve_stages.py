"""The serve layer's stage instruments and spans, on served runs on the CPU.

Per session, in the scheduler registry: ``serve.h2d_s`` (a chunk landing
on the device, its pull excluded), ``serve.backpressure_s`` (the
acquisition thread blocked on a full ring), ``serve.coalesce_s`` and
``serve.step_s`` (one sample per group) and ``serve.finalize_s`` (one per
session). With a tracer enabled, each stage is a ``serve.*`` span
carrying the request's identifiers.
"""

import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.core.denoise import DenoiseConfig
from repro.data.prism import PrismSource
from repro.serve import FaultPlan, Session, SessionScheduler

WAIT = 300  # bounded waits: the first step pays the jit compile


def _cfg(**kw):
    base = dict(
        num_groups=4, frames_per_group=20, height=16, width=64, backend="xla",
    )
    base.update(kw)
    return DenoiseConfig(**base)


def _groups(cfg, seed=3):
    return list(PrismSource(cfg, seed=seed).groups())


class _Recording(SessionScheduler):
    """Keeps each admitted session's scheduler-side record by name."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.acts = {}

    def _on_submitted(self, handle, act, ex):
        self.acts[act.name] = act


def _sleeping(groups, sleep_s, gate=None):
    if gate is not None:
        gate.wait()
    for g in groups:
        time.sleep(sleep_s)
        yield g


def test_h2d_excludes_the_pull_that_transfer_includes():
    cfg = _cfg()
    groups = _groups(cfg)
    with SessionScheduler(slots_per_executor=1, max_executors=1) as sched:
        h = sched.submit(Session(cfg, _sleeping(groups, 0.03), name="slow"))
        h.result(timeout=WAIT)
        reg = sched.metrics
        transfer = reg.value("serve.transfer_s", session="slow")
        h2d = reg.value("serve.h2d_s", session="slow")
    assert transfer >= 0.03 * len(groups)
    assert 0.0 < h2d < transfer
    # the pull alone accounts for the difference, so it is at least the sleeps
    assert transfer - h2d >= 0.03 * len(groups)


def test_latency_is_ring_dwell_plus_step_per_session():
    cfg = _cfg()
    with _Recording(slots_per_executor=2, max_executors=1) as sched:
        hs = [
            sched.submit(Session(cfg, iter(_groups(cfg, seed=s)), name=f"d{s}"))
            for s in range(3)
        ]
        for h in hs:
            h.result(timeout=WAIT)
        reg = sched.metrics
        for name, act in sched.acts.items():
            lat = reg.histogram("serve.latency_s", session=name)
            step = reg.histogram("serve.step_s", session=name)
            assert lat.count == step.count == cfg.num_groups
            assert lat.sum - step.sum == pytest.approx(act.ring.stats.dwell_s, abs=1e-9)


def test_one_finalize_sample_per_session():
    cfg = _cfg()
    with SessionScheduler(slots_per_executor=2, max_executors=1) as sched:
        hs = [
            sched.submit(Session(cfg, iter(_groups(cfg, seed=s)), name=f"f{s}"))
            for s in range(3)
        ]
        for h in hs:
            h.result(timeout=WAIT)
        for s in range(3):
            fin = sched.metrics.histogram("serve.finalize_s", session=f"f{s}")
            assert fin.count == 1
            assert fin.sum > 0.0


def test_coalesce_is_zero_where_the_cohort_fills_the_executor():
    # one slot: every ready cohort is the whole executor, nothing to wait for
    cfg = _cfg()
    with SessionScheduler(slots_per_executor=1, max_executors=1, coalesce_ms=50.0) as sched:
        sched.submit(Session(cfg, iter(_groups(cfg)), name="full")).result(timeout=WAIT)
        co = sched.metrics.histogram("serve.coalesce_s", session="full")
        assert co.count == cfg.num_groups
        assert co.stats()["max"] == 0.0


def test_coalesce_holds_the_wait_for_a_straggler():
    # two slots, one tenant slow: the fast tenant's partial cohorts wait
    # the window (20 ms) for the straggler, each group one sample
    cfg = _cfg()
    gate = threading.Event()
    with SessionScheduler(slots_per_executor=2, max_executors=1, coalesce_ms=20.0) as sched:
        fast = sched.submit(Session(cfg, _sleeping(_groups(cfg, 1), 0.0, gate), name="fast"))
        slow = sched.submit(Session(cfg, _sleeping(_groups(cfg, 2), 0.1, gate), name="slow"))
        deadline = time.monotonic() + WAIT
        while (fast.status, slow.status) != ("active", "active"):
            assert time.monotonic() < deadline
            time.sleep(0.001)
        gate.set()
        fast.result(timeout=WAIT)
        slow.result(timeout=WAIT)
        co = sched.metrics.histogram("serve.coalesce_s", session="fast")
    assert co.count == cfg.num_groups
    assert 0.015 <= co.stats()["max"] < 0.5


def _staggered_outputs(cfg, coalesce_ms):
    """Two tenants on four slots, one of them 30 ms behind per group."""
    gate = threading.Event()
    with SessionScheduler(
        slots_per_executor=4, max_executors=1, coalesce_ms=coalesce_ms
    ) as sched:
        hs = [
            sched.submit(Session(cfg, _sleeping(_groups(cfg, s), sleep_s, gate), name=f"g{s}"))
            for s, sleep_s in ((1, 0.0), (2, 0.03))
        ]
        deadline = time.monotonic() + WAIT
        while any(h.status != "active" for h in hs):
            assert time.monotonic() < deadline
            time.sleep(0.001)
        gate.set()
        outs = [np.asarray(h.result(timeout=WAIT)[0]) for h in hs]
        reg = sched.metrics
        coalesce = [reg.histogram("serve.coalesce_s", session=f"g{s}") for s in (1, 2)]
        skips = reg.value("serve.coalesce_skips", executor="ex0")
    return outs, coalesce, skips


def test_coalesce_skips_the_window_when_the_slots_cannot_fill():
    # two steppable tenants can never fill four slots: the fast tenant's
    # partial cohorts step at once instead of waiting 50 ms for the other
    cfg = _cfg()
    outs, coalesce, skips = _staggered_outputs(cfg, coalesce_ms=50.0)
    for co in coalesce:
        assert co.count == cfg.num_groups
        assert co.stats()["max"] == 0.0
    assert skips > 0
    plain, _, plain_skips = _staggered_outputs(cfg, coalesce_ms=0.0)
    assert plain_skips == 0  # no window configured: nothing to skip
    for a, b in zip(outs, plain):
        np.testing.assert_array_equal(a, b)


def test_a_stream_ending_mid_window_ends_the_wait():
    # three slots, all steppable: the fast tenant's second group opens the
    # window for "held" and "short"; "short" then ends its stream, so no
    # full cohort can form any more and the wait ends long before 10 s
    cfg = _cfg()
    gate, end_short, release_held = (threading.Event() for _ in range(3))

    def held():
        gs = _groups(cfg, 2)
        gate.wait()
        yield gs[0]
        release_held.wait()
        yield from gs[1:]

    def short():
        gate.wait()
        yield _groups(cfg, 3)[0]
        end_short.wait()

    with _Recording(slots_per_executor=3, max_executors=1, coalesce_ms=10_000.0) as sched:
        hs = [
            sched.submit(Session(cfg, _sleeping(_groups(cfg, 1), 0.0, gate), name="fast")),
            sched.submit(Session(cfg, held(), name="held")),
            sched.submit(Session(cfg, short(), name="short")),
        ]
        deadline = time.monotonic() + WAIT
        while any(h.status != "active" for h in hs):
            assert time.monotonic() < deadline
            time.sleep(0.001)
        gate.set()
        fast = sched.acts["fast"]
        while fast.steps < 1 or len(fast.ring) == 0:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        time.sleep(0.5)  # the executor now waits in the window
        end_short.set()
        hs[0].result(timeout=WAIT)
        release_held.set()
        for h in hs[1:]:
            h.result(timeout=WAIT)
        co = sched.metrics.histogram("serve.coalesce_s", session="fast")
    assert co.count == cfg.num_groups
    assert 0.25 <= co.stats()["max"] < 5.0


def test_backpressure_counts_a_producer_held_by_a_stalled_executor(fleet_factory):
    cfg = _cfg(num_groups=6)
    plan = FaultPlan().stall("ex0", at_step=1)
    fleet = fleet_factory(
        slots_per_executor=1, max_executors=1, faults=plan, checkpoint_dir=None
    )
    h = fleet.submit(Session(cfg, iter(_groups(cfg)), name="held", num_slots=1))
    assert plan.wait_stalled("ex0", timeout=WAIT)
    time.sleep(0.3)  # the one-slot ring fills and the producer blocks
    plan.release("ex0")
    h.result(timeout=WAIT)
    assert fleet.metrics.value("serve.backpressure_s", session="held") >= 0.2


def test_no_backpressure_while_the_ring_has_room():
    cfg = _cfg(num_groups=6)
    with SessionScheduler(slots_per_executor=1, max_executors=1) as sched:
        h = sched.submit(Session(cfg, iter(_groups(cfg)), name="roomy", num_slots=8))
        h.result(timeout=WAIT)
        assert sched.metrics.value("serve.backpressure_s", session="roomy") == 0.0
        # the counter exists: zero is a reading, not an absence
        assert "serve.backpressure_s{session=roomy}" in sched.metrics.snapshot()


# ---------------------------------------------------------------------------
# Spans under an enabled tracer on a frozen clock.
# ---------------------------------------------------------------------------


@pytest.fixture
def enabled_tracer(fake_clock):
    tr = obs.get_tracer()
    was_enabled, old_clock = tr.enabled, tr.clock
    tr.clear()
    obs.configure(enabled=True, clock=fake_clock)
    yield tr
    obs.configure(enabled=was_enabled, clock=old_clock)
    tr.clear()


def _served_trace(tr, sessions=2, slots=2):
    cfg = _cfg()
    gate = threading.Event()
    with SessionScheduler(slots_per_executor=slots, max_executors=1) as sched:
        hs = [
            sched.submit(Session(cfg, _sleeping(_groups(cfg, s), 0.0, gate), name=f"t{s}"))
            for s in range(sessions)
        ]
        deadline = time.monotonic() + WAIT
        while any(h.status != "active" for h in hs):
            assert time.monotonic() < deadline
            time.sleep(0.001)
        gate.set()
        outs = [np.asarray(h.result(timeout=WAIT)[0]) for h in hs]
    return cfg, outs, [e for e in tr.events() if e["kind"] == "span"]


def test_stage_spans_carry_the_request_identifiers(enabled_tracer):
    cfg, _, spans = _served_trace(enabled_tracer)
    names = {e["name"] for e in spans}
    assert {"serve.h2d", "serve.admit", "serve.dispatch", "serve.block",
            "serve.finalize", "serve.wait"} <= names
    assert "serve.cohort" not in names
    h2d = [e for e in spans if e["name"] == "serve.h2d"]
    assert sorted((e["args"]["session"], e["args"]["group"]) for e in h2d) == [
        (f"t{s}", g) for s in range(2) for g in range(cfg.num_groups)
    ]
    assert all(e["thread"].startswith("serve-src-") for e in h2d)
    admits = [e for e in spans if e["name"] == "serve.admit"]
    assert sorted(e["args"]["session"] for e in admits) == ["t0", "t1"]
    assert all(e["args"]["group"] == 0 and e["args"]["executor"] == "ex0" for e in admits)
    folded = {}
    for e in spans:
        if e["name"] == "serve.dispatch":
            a = e["args"]
            assert a["executor"] == "ex0" and a["size"] == len(a["session"]) == len(a["group"])
            assert a["kind"] in ("lone", "partial", "full")
            assert a["kind"] == {1: "lone", 2: "full"}.get(a["size"])
            for sess, g in zip(a["session"], a["group"]):
                folded.setdefault(sess, []).append(g)
    # every group of every session folded once, in order
    assert folded == {f"t{s}": list(range(cfg.num_groups)) for s in range(2)}
    fin = [e for e in spans if e["name"] == "serve.finalize"]
    assert sorted((e["args"]["session"], e["args"]["groups"]) for e in fin) == [
        ("t0", cfg.num_groups), ("t1", cfg.num_groups)
    ]
    assert all(e["args"] == {"executor": "ex0"} for e in spans if e["name"] == "serve.wait")


def test_dispatch_and_block_are_siblings_in_order(enabled_tracer):
    _, _, spans = _served_trace(enabled_tracer)
    ex = sorted(
        (e for e in spans if e["name"] in ("serve.dispatch", "serve.block")),
        key=lambda e: e["seq0"],
    )
    assert ex and len(ex) % 2 == 0
    for d, b in zip(ex[::2], ex[1::2]):
        assert (d["name"], b["name"]) == ("serve.dispatch", "serve.block")
        assert d["args"] == b["args"] and d["tid"] == b["tid"]
        # the dispatch closes before the block opens: siblings, not nested
        assert d["seq1"] < b["seq0"]
    # nothing on the executor thread encloses a block
    for b in ex[1::2]:
        assert not any(
            e["tid"] == b["tid"] and e["seq0"] < b["seq0"] and e["seq1"] > b["seq1"]
            for e in spans
        )


def test_stage_trace_exports_nested_chrome_json_and_same_output(enabled_tracer):
    cfg, traced, _ = _served_trace(enabled_tracer)
    doc = enabled_tracer.export_chrome()
    events = obs.validate_chrome_trace(doc)  # B/E pair up per thread
    assert {"serve.dispatch", "serve.block", "serve.h2d"} <= {e["name"] for e in events}
    enabled_tracer.clear()
    obs.configure(enabled=False)
    _, plain, spans = _served_trace(enabled_tracer)
    assert spans == []
    for a, b in zip(traced, plain):
        np.testing.assert_array_equal(a, b)


def test_disabled_stages_record_no_span_but_every_instrument():
    tr = obs.get_tracer()
    if tr.enabled:  # REPRO_OBS set in the environment: nothing to assert
        pytest.skip("default tracer enabled via REPRO_OBS")
    before = len(tr.events())
    assert obs.span("serve.dispatch", "serve") is obs.span("serve.block", "serve")
    cfg = _cfg()
    with SessionScheduler(slots_per_executor=1, max_executors=1) as sched:
        sched.submit(Session(cfg, iter(_groups(cfg)), name="quiet")).result(timeout=WAIT)
        snap = sched.metrics.snapshot()
    assert len(tr.events()) == before
    for name in ("serve.coalesce_s", "serve.step_s", "serve.latency_s"):
        assert snap[f"{name}{{session=quiet}}"]["count"] == cfg.num_groups
    assert snap["serve.finalize_s{session=quiet}"]["count"] == 1
    assert snap["serve.h2d_s{session=quiet}"]["value"] > 0.0
