"""Fault-tolerance runtime: heartbeats, straggler EWMA, supervised restart
resuming from the latest checkpoint, elastic re-shard."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.distributed.sharding import ParamSpec
from repro.runtime import HeartbeatMonitor, StragglerDetector, Supervisor
from repro.runtime.elastic import available_mesh, elastic_reshard


class TestHeartbeat:
    def test_dead_detection(self):
        hb = HeartbeatMonitor(timeout_s=10)
        hb.beat("w0", now=0.0)
        hb.beat("w1", now=0.0)
        hb.beat("w0", now=8.0)
        assert hb.dead(now=15.0) == ["w1"]
        assert hb.dead(now=5.0) == []

    def test_evict(self):
        hb = HeartbeatMonitor(timeout_s=1)
        hb.beat("w0", now=0.0)
        hb.evict("w0")
        assert hb.dead(now=100.0) == []


class TestStraggler:
    def test_flags_slow_worker(self):
        sd = StragglerDetector(threshold=1.5, warmup_steps=3)
        for _ in range(5):
            for w in ("w0", "w1", "w2", "w3"):
                sd.record(w, 1.0)
            sd.record("slow", 3.0)
        assert sd.stragglers() == ["slow"]

    def test_warmup_suppresses_flapping(self):
        sd = StragglerDetector(threshold=1.5, warmup_steps=3)
        sd.record("w0", 1.0)
        sd.record("w1", 1.0)
        sd.record("spike", 10.0)  # single spike, below warmup
        assert sd.stragglers() == []

    def test_recovery_unflags(self):
        sd = StragglerDetector(threshold=1.5, warmup_steps=2, alpha=0.9)
        for _ in range(4):
            sd.record("w0", 1.0)
            sd.record("w1", 1.0)
            sd.record("w2", 5.0)
        assert "w2" in sd.stragglers()
        for _ in range(10):
            sd.record("w0", 1.0)
            sd.record("w1", 1.0)
            sd.record("w2", 1.0)
        assert sd.stragglers() == []


class TestHeartbeatBoundaries:
    """``dead()`` uses a strict ``now - last > timeout``: a worker seen
    exactly ``timeout`` ago is still alive (the fleet's eviction edge)."""

    def test_exact_timeout_is_alive(self):
        hb = HeartbeatMonitor(timeout_s=10)
        hb.beat("w0", now=5.0)
        assert hb.dead(now=15.0) == []           # == timeout: alive
        assert hb.dead(now=15.0 + 1e-9) == ["w0"]  # just past: dead

    def test_beat_refreshes_deadline(self):
        hb = HeartbeatMonitor(timeout_s=10)
        hb.beat("w0", now=0.0)
        hb.beat("w0", now=9.0)
        assert hb.dead(now=15.0) == []
        assert hb.dead(now=19.5) == ["w0"]

    def test_unknown_worker_never_dead(self):
        hb = HeartbeatMonitor(timeout_s=1)
        assert hb.dead(now=1e9) == []
        hb.evict("never-seen")  # idempotent on unknowns
        assert hb.workers() == []

    def test_workers_sorted_and_evict_is_idempotent(self):
        hb = HeartbeatMonitor(timeout_s=1)
        hb.beat("b", now=0.0)
        hb.beat("a", now=0.0)
        assert hb.workers() == ["a", "b"]
        hb.evict("a")
        hb.evict("a")
        assert hb.workers() == ["b"]
        assert hb.dead(now=100.0) == ["b"]


class TestStragglerProperties:
    def test_ewma_matches_manual_fold(self):
        """``ewma`` is exactly the recurrence
        ``alpha * x + (1 - alpha) * prev`` seeded with the first sample."""
        sd = StragglerDetector(alpha=0.3)
        samples = [1.0, 4.0, 0.5, 2.25, 8.0]
        expect = None
        for x in samples:
            sd.record("w", x)
            expect = x if expect is None else 0.3 * x + 0.7 * expect
            assert sd.ewma("w") == pytest.approx(expect, rel=1e-12)

    def test_threshold_boundary_is_strict(self):
        """A worker sitting exactly at ``threshold * median`` is NOT
        flagged — only strictly above trips the detector."""
        sd = StragglerDetector(threshold=2.0, warmup_steps=1, alpha=1.0)
        for w, v in (("a", 1.0), ("b", 1.0), ("c", 1.0)):
            sd.record(w, v)
        sd.record("edge", 2.0)   # median of {1,1,1,2} = 1.0; 2.0 == 2*1.0
        assert sd.stragglers() == []
        sd.record("edge", 2.0 + 1e-9)
        assert sd.stragglers() == ["edge"]

    def test_median_even_and_odd_counts(self):
        sd = StragglerDetector(threshold=1.5, warmup_steps=1, alpha=1.0)
        sd.record("a", 1.0)
        sd.record("b", 3.0)
        assert sd._median() == pytest.approx(2.0)  # even: midpoint
        sd.record("c", 100.0)
        assert sd._median() == pytest.approx(3.0)  # odd: middle value

    def test_all_zero_durations_flag_nobody(self):
        """A fleet whose steps all report 0s (virtual-clock runs with no
        scripted slow-down) must not divide by a zero median."""
        sd = StragglerDetector(threshold=1.5, warmup_steps=1)
        for w in ("a", "b", "c"):
            sd.record(w, 0.0)
        assert sd.stragglers() == []

    def test_forget_removes_history_and_median_skew(self):
        """Evicting a straggler must drop it from the pool median so its
        replacement is judged against healthy peers only."""
        sd = StragglerDetector(threshold=1.5, warmup_steps=2, alpha=1.0)
        for _ in range(3):
            sd.record("w0", 1.0)
            sd.record("w1", 1.0)
            sd.record("slow", 10.0)
        assert sd.stragglers() == ["slow"]
        sd.forget("slow")
        assert sd.ewma("slow") is None
        assert sd.stragglers() == []
        # a fresh worker under the old skewed median would have hidden;
        # against the healthy median it is flagged once warmed up
        sd.record("slow2", 4.0)
        sd.record("slow2", 4.0)
        assert sd.stragglers() == ["slow2"]

    def test_warmup_boundary(self):
        sd = StragglerDetector(threshold=1.5, warmup_steps=3, alpha=1.0)
        for w in ("a", "b"):
            for _ in range(5):
                sd.record(w, 1.0)
        sd.record("slow", 9.0)
        sd.record("slow", 9.0)
        assert sd.stragglers() == []      # 2 < warmup_steps
        sd.record("slow", 9.0)
        assert sd.stragglers() == ["slow"]  # exactly at warmup


class TestSupervisor:
    def test_restart_resumes_from_checkpoint(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=5)
        crashed = {"done": False}

        def step_fn(state, step):
            if step == 7 and not crashed["done"]:
                crashed["done"] = True
                raise RuntimeError("node lost")
            return {"x": state["x"] + 1}

        sup = Supervisor(mgr, max_restarts=2, save_every=2)
        state, history = sup.run({"x": jnp.asarray(0)}, step_fn, num_steps=10)
        assert int(state["x"]) == 10  # every step applied exactly once
        assert any(h.startswith("fail@7") for h in history)
        assert any(h.startswith("restore@") for h in history)

    def test_gives_up_after_max_restarts(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))

        def bad(state, step):
            raise RuntimeError("always fails")

        sup = Supervisor(mgr, max_restarts=2, save_every=1)
        with pytest.raises(RuntimeError, match="exceeded"):
            sup.run({"x": jnp.asarray(0)}, bad, num_steps=3)

    def test_exhaustion_is_exact_and_history_complete(self, tmp_path):
        """The budget is strict: ``max_restarts`` failures are absorbed,
        the ``max_restarts + 1``-th raises, and the history names every
        failure site."""
        mgr = CheckpointManager(str(tmp_path), keep=5)
        fails = {"n": 0}

        def step_fn(state, step):
            if step == 1 and fails["n"] < 2:
                fails["n"] += 1
                raise RuntimeError("transient")
            return {"x": state["x"] + 1}

        sup = Supervisor(mgr, max_restarts=2, save_every=1)
        state, history = sup.run({"x": jnp.asarray(0)}, step_fn, num_steps=4)
        assert int(state["x"]) == 4
        assert sum(h.startswith("fail@1") for h in history) == 2

        fails["n"] = -10**6  # now every visit to step 1 fails
        with pytest.raises(RuntimeError, match="exceeded 2 restarts"):
            sup2 = Supervisor(
                CheckpointManager(str(tmp_path / "b"), keep=5),
                max_restarts=2,
                save_every=1,
            )
            sup2.run(
                {"x": jnp.asarray(0)},
                lambda s, k: (_ for _ in ()).throw(RuntimeError("always")),
                num_steps=3,
            )

    def test_on_restart_hook_runs_per_restore(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=5)
        crashed = {"done": False}
        hook_calls = []

        def step_fn(state, step):
            if step == 5 and not crashed["done"]:
                crashed["done"] = True
                raise RuntimeError("node lost")
            return {"x": state["x"] + 1}

        def on_restart(state):
            hook_calls.append(int(state["x"]))
            return state

        sup = Supervisor(mgr, max_restarts=2, save_every=2)
        state, history = sup.run(
            {"x": jnp.asarray(0)}, step_fn, num_steps=8, on_restart=on_restart
        )
        assert int(state["x"]) == 8
        assert len(hook_calls) == 1
        assert any(h.startswith("restore@") for h in history)

    def test_save_cadence_bounds_replay(self, tmp_path):
        """With ``save_every=n`` a crash replays at most ``n`` steps: the
        work counter after recovery shows every step applied exactly once
        plus at most ``n`` replayed ones."""
        mgr = CheckpointManager(str(tmp_path), keep=10)
        calls = []
        crashed = {"done": False}

        def step_fn(state, step):
            if step == 7 and not crashed["done"]:
                crashed["done"] = True
                raise RuntimeError("boom")
            calls.append(step)
            return {"x": state["x"] + 1}

        sup = Supervisor(mgr, max_restarts=1, save_every=3)
        state, _ = sup.run({"x": jnp.asarray(0)}, step_fn, num_steps=10)
        assert int(state["x"]) == 10          # exactly-once effect on state
        replayed = len(calls) - 10
        assert 0 <= replayed <= 3             # bounded by the cadence


class TestElastic:
    def test_reshard_single_device(self):
        spec = {"w": ParamSpec((8, 16), ("embed", "mlp"))}
        state = {"w": jnp.arange(128, dtype=jnp.float32).reshape(8, 16)}
        mesh = available_mesh(("data", "model"))
        moved = elastic_reshard(state, spec, mesh)
        np.testing.assert_array_equal(np.asarray(moved["w"]), np.asarray(state["w"]))

    def test_reshard_multi_device_subprocess(self):
        """Shrink 8 -> 4 devices: values preserved, shardings re-derived."""
        import subprocess, sys, textwrap

        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp, numpy as np
            from repro.distributed.sharding import ParamSpec, named_shardings
            from repro.runtime.elastic import elastic_reshard
            spec = {"w": ParamSpec((8, 16), ("embed", "mlp"))}
            state = {"w": jnp.arange(128, dtype=jnp.float32).reshape(8, 16)}
            from repro.mesh import make_mesh
            mesh8 = make_mesh((4, 2), ("data", "model"))
            sharded = jax.tree_util.tree_map(
                jax.device_put, state, named_shardings(spec, mesh8))
            mesh4 = make_mesh((2, 2), ("data", "model"),
                              devices=jax.devices()[:4])
            moved = elastic_reshard(sharded, spec, mesh4)
            np.testing.assert_array_equal(np.asarray(moved["w"]),
                                          np.asarray(state["w"]))
            assert len(moved["w"].sharding.device_set) == 4
            print("ELASTIC_OK")
        """)
        env = dict(**__import__("os").environ)
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env=env, timeout=300,
        )
        assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]
