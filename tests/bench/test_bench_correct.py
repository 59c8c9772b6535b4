"""What decides ``correct``: the copied plain reference against the served
path, the lower-precision control, and whole runs of the harness (past
its look for a chip) with the timed path broken underneath.

Small sizes on the CPU, Pallas in interpret mode; on the chip the control
is read at the cell's own size with ``bench/control.py``.
"""

import dataclasses
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cell as cell_mod, control, reference, spec
from bench.pool import acquisition_groups, make_pool

SMALL = dict(frames_per_group=16, height=16, width=128, backend="pallas")
OFFSET = 4096.0


def _small(name: str, **traffic) -> spec.Cell:
    cell = spec.load_cell(name)
    config = {**cell.config, "denoise": {**cell.config["denoise"], **SMALL}}
    config["scheduler"] = {**config["scheduler"], "slots_per_executor": 3,
                           "max_sessions": 6, "max_waiting": 3}
    t = {**cell.traffic, "cameras": 3, "pool_groups": 4, "check_share": 1.0,
         "lead_in_s": 0.5, **traffic}
    if t["frame_interval_us"] is not None:
        t["frame_interval_us"] = 2000.0
    return dataclasses.replace(cell, config=config, traffic=t)


def _run(cell, seed=2**33 + 17, seconds=1.0):
    return cell_mod.run_cell(cell, seed, seconds, False, t_start=time.perf_counter(),
                             require_tpu=False)


def test_reference_matches_the_served_path_exactly():
    from repro.core.denoise import DenoiseConfig
    from repro.serve import Session, SessionScheduler

    d = {**spec.load_cell("prism_u16.paced").config["denoise"], **SMALL}
    cfg = DenoiseConfig(**d)
    seed = 2**31 + 5
    pool = make_pool(seed, 4, cfg.frames_per_group, cfg.height, cfg.width)
    acqs = [acquisition_groups(seed, c, 0, cfg.num_groups, len(pool)) for c in range(3)]
    with SessionScheduler(slots_per_executor=3, max_executors=1) as sched:
        handles = [sched.submit(Session(cfg, [pool[i] for i in idx], name=f"s{c}"))
                   for c, idx in enumerate(acqs)]
        outs = [np.asarray(h.result(timeout=300)[0]) for h in handles]
    for idx, out in zip(acqs, outs):
        ref = reference.pair_average([reference.diffs(pool[i], OFFSET) for i in idx])
        assert reference.max_abs_err(out, ref) == 0.0


def test_the_bf16_control_fails_the_limit():
    seed = 2**32 + 3
    pool = make_pool(seed, 4, 16, 16, 128)
    errs = control.control_errors(pool, groups=8, offset=OFFSET, seed=seed, acquisitions=3)
    # values near the 4096 offset sit 32 apart in bfloat16
    assert min(errs) > 10 * (cell_mod.MAX_ABS_ERR_LIMIT + 1)


def test_max_abs_err_refuses_wrong_shapes_and_non_finite():
    ref = np.zeros((2, 2), np.float32)
    assert reference.max_abs_err(np.zeros((2, 3)), ref) == float("inf")
    assert reference.max_abs_err(np.full((2, 2), np.nan), ref) == float("inf")
    assert reference.max_abs_err(np.ones((2, 2)), ref) == 1.0


def test_pool_is_made_from_the_seed():
    a = make_pool(2**40 + 1, 2, 4, 8, 128)
    b = make_pool(2**40 + 1, 2, 4, 8, 128)
    c = make_pool(1, 2, 4, 8, 128)
    assert a.dtype == np.uint16 and a.shape == (2, 4, 8, 128)
    np.testing.assert_array_equal(a, b)
    assert (a != c).any() and a.max() <= 4095


@pytest.mark.parametrize("traffic", ["paced", "saturate"])
def test_a_sound_run_is_correct(traffic):
    result, run = _run(_small(f"prism_u16.{traffic}"))
    assert result["correct"], result["checks"]
    assert result["checks"]["compared"]["value"] >= 3
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in _small(f"prism_u16.{traffic}").end_to_end}
    assert list(result)[-1] == "checks"


def _step_unchanged(orig_step, orig_finalize):
    def step(self, state, group_frames, *, step_index):
        return state

    return step, orig_finalize


def _half_the_groups(orig_step, orig_finalize):
    def step(self, state, group_frames, *, step_index):
        return state if step_index % 2 else orig_step(self, state, group_frames,
                                                      step_index=step_index)

    def finalize(self, state, *, steps=None):
        return self._scaled(state, self.config.num_groups // 2)

    return step, finalize


def _answer_altered(orig_step, orig_finalize):
    def finalize(self, state, *, steps=None):
        out = orig_finalize(self, state, steps=steps)
        return out.at[(0,) * out.ndim].set(jnp.nextafter(out[(0,) * out.ndim], jnp.inf))

    return orig_step, finalize


@pytest.mark.parametrize("fault", [_step_unchanged, _half_the_groups, _answer_altered])
def test_a_broken_path_is_not_correct(monkeypatch, fault):
    from repro.denoise.pair_average import PairAverageFilter

    step, finalize = fault(PairAverageFilter.step, PairAverageFilter.finalize)
    monkeypatch.setattr(PairAverageFilter, "step", step)
    monkeypatch.setattr(PairAverageFilter, "finalize", finalize)
    result, _ = _run(_small("prism_u16.paced"))
    assert not result["correct"]
    assert result["checks"]["max_abs_err"]["value"] > cell_mod.MAX_ABS_ERR_LIMIT


def test_a_cell_asking_for_more_chips_than_found_exits():
    cell = dataclasses.replace(_small("prism_u16.paced"), chips=len(jax.devices()) + 3)
    with pytest.raises(SystemExit):
        _run(cell)


def test_run_exits_non_zero_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, str(spec.ROOT / "bench" / "run.py"), "--workload", "prism_u16.paced",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
