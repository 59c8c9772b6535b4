"""Tuning layer: shared budget model, plan cache contract, measured
autotuner plumbing, heuristic bit-compatibility, and the no-retrace /
resolve-once guarantees of ``tile_plan``."""

import json

import jax
import numpy as np
import pytest

import jax.numpy as jnp

from repro import tune
from repro.core.denoise import DenoiseConfig, StreamingDenoiser
from repro.core.streaming import run_inline, run_pipelined
from repro.kernels import ops
from repro.kernels.denoise_stream import (
    _pick_pair_tile,
    _pick_row_tile,
    alg3_subtract_average,
)
from repro.tune import budget
from repro.tune.plan import SCHEMA_VERSION, exec_key, family_key


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Every test gets its own persistent cache and a clean plan memo."""
    monkeypatch.setenv("REPRO_TUNE_CACHE_PATH", str(tmp_path / "plans.json"))
    tune.clear_plan_memo()
    yield
    tune.clear_plan_memo()


def _cfg(**kw):
    base = dict(num_groups=4, frames_per_group=20, height=16, width=64,
                backend="xla")
    base.update(kw)
    return DenoiseConfig(**base)


def _groups(cfg, seed=3):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 4096, (cfg.frames_per_group, cfg.height, cfg.width))
        .astype(np.uint16)
        for _ in range(cfg.num_groups)
    ]


# ---------------------------------------------------------------------------
# Shared budget model: divisor/budget invariants, awkward shapes, errors.
# ---------------------------------------------------------------------------


AWKWARD = [(97, 66, 256), (101, 97, 256), (500, 80, 256), (33, 66, 640),
           (7, 13, 2048), (1, 1, 128)]


@pytest.mark.parametrize("family", sorted(budget.KERNEL_FAMILIES))
@pytest.mark.parametrize("p,h,w", AWKWARD)
def test_resolve_tiles_divides_and_fits(family, p, h, w):
    window = 5 if family == "median_combine" else 1
    th, tp = budget.resolve_tiles(family, p, h, w, window=window)
    assert h % th == 0 and p % tp == 0
    bb = budget.block_bytes(family, th, tp, w, window=window)
    # within budget, unless even the smallest legal row tile overflows
    # (then that tile). "ema" is pinned to the legacy pick for
    # bit-compatibility (its Chan merge makes pair_tile numerics-visible),
    # so it may overshoot the corrected accounting by a bounded factor.
    cap = budget.VMEM_BUDGET * (2 if family == "ema" else 1)
    assert bb <= cap or th == budget.legal_row_tiles(family, h)[0]


def test_resolve_tiles_rejects_non_dividing_overrides():
    with pytest.raises(ValueError, match="row_tile 7 must divide H=8"):
        budget.resolve_tiles("stream", 10, 8, 32, row_tile=7)
    with pytest.raises(ValueError, match="pair_tile 3 must divide N/2=10"):
        budget.resolve_tiles("stream", 10, 8, 32, pair_tile=3)
    with pytest.raises(ValueError, match="kernel family"):
        budget.resolve_tiles("nope", 10, 8, 32)


def test_kernel_rejects_non_dividing_override_end_to_end():
    frames = jnp.ones((2, 6, 8, 32), jnp.float32)
    with pytest.raises(ValueError, match="row_tile 5 must divide H=8"):
        alg3_subtract_average(frames, row_tile=5, interpret=True)


def test_property_resolve_tiles_exact_divisors_within_budget():
    pytest.importorskip(
        "hypothesis", reason="dev-only dependency (see requirements-dev.txt)"
    )
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=200, deadline=None)
    @given(
        family=st.sampled_from(sorted(budget.KERNEL_FAMILIES)),
        p=st.integers(1, 2048),
        h=st.integers(1, 512),
        w=st.sampled_from([24, 128, 256, 640, 2048]),
        window=st.integers(1, 9),
        in_dtype=st.sampled_from(["uint16", "float32", "bfloat16"]),
        budget_bytes=st.sampled_from(
            [2**14, 2**18, budget.VMEM_BUDGET, 2**24]
        ),
    )
    def check(family, p, h, w, window, in_dtype, budget_bytes):
        th, tp = budget.resolve_tiles(
            family, p, h, w, in_dtype=in_dtype, window=window,
            vmem_budget=budget_bytes,
        )
        assert 1 <= th <= h and h % th == 0
        assert 1 <= tp <= p and p % tp == 0
        bb = budget.block_bytes(
            family, th, tp, w, in_dtype=in_dtype, window=window
        )
        # over budget only when even the smallest legal row tile overflows
        smallest = budget.legal_row_tiles(family, h, in_dtype=in_dtype)[0]
        # ema at the default budget runs the bit-compat legacy pick
        # (bounded <= 2x overshoot); everything else fits exactly
        if family == "ema" and budget_bytes == budget.VMEM_BUDGET:
            assert bb <= 2 * budget_bytes or th == smallest
        else:
            assert bb <= budget_bytes or th == smallest

    check()


#: native sublane rows of one VMEM tile: 8 of f32, 16 of 16-bit, 32 of 8-bit
SUBLANE_ROWS = {"float32": 8, "bfloat16": 16, "uint16": 16, "uint8": 32}


def test_property_resolve_tiles_sublane_aligned():
    """Picked row tiles sit on the input dtype's native sublane tiling (a
    f32 accumulator never allows fewer than 8 rows), or span the full
    height: the only row blocks Mosaic takes without relayouts."""
    pytest.importorskip(
        "hypothesis", reason="dev-only dependency (see requirements-dev.txt)"
    )
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=300, deadline=None)
    @given(
        family=st.sampled_from(sorted(budget.KERNEL_FAMILIES)),
        p=st.integers(1, 1024),
        h=st.integers(1, 1024),
        w=st.sampled_from([128, 256, 384, 2048]),
        in_dtype=st.sampled_from(sorted(SUBLANE_ROWS)),
        budget_bytes=st.sampled_from([2**14, 2**18, budget.VMEM_BUDGET]),
    )
    def check(family, p, h, w, in_dtype, budget_bytes):
        th, _ = budget.resolve_tiles(
            family, p, h, w, in_dtype=in_dtype, vmem_budget=budget_bytes
        )
        has_input = budget.KERNEL_FAMILIES[family].in_planes > 0
        align = SUBLANE_ROWS[in_dtype] if has_input else 8
        assert th == h or (th % align == 0 and h % th == 0), (th, align)

    check()


def test_shared_model_matches_legacy_picks_at_production_shapes():
    """The corrected operand accounting coincides with the old 3-tile
    model exactly at the paper/production shapes (u16 and f32 inputs) —
    the quantitative backing for heuristic-mode bit-identity on the
    tile-sensitive (EMA Chan-merge) kernel."""
    for p, h, w in [(500, 80, 256), (100, 80, 256), (10, 16, 64), (3, 8, 32)]:
        th_legacy = _pick_row_tile(h, w)
        tp_legacy = _pick_pair_tile(p, th_legacy, w)
        for in_dtype in ("uint16", "float32"):
            for family in ("stream", "ema"):
                assert budget.resolve_tiles(
                    family, p, h, w, in_dtype=in_dtype
                ) == (th_legacy, tp_legacy), (family, p, h, w, in_dtype)


def test_ema_heuristic_pinned_to_legacy_pick():
    """The EMA kernel's Chan merge makes pair_tile numerics-visible, so
    its heuristic stays pinned to the pre-tuner pick wherever that pick
    is a legal row tile within 2x the budget — including shapes where the
    corrected accounting would diverge (p=96, f32 input: corrected budget
    would pick 6, legacy picks 8)."""
    for p, h, w in [(96, 80, 256), (56, 80, 256), (500, 80, 256)]:
        th_legacy = _pick_row_tile(h, w)
        tp_legacy = _pick_pair_tile(p, th_legacy, w)
        for in_dtype in ("uint16", "float32"):
            assert budget.resolve_tiles("ema", p, h, w, in_dtype=in_dtype) \
                == (th_legacy, tp_legacy)
    # and the pallas kernel's output is bitwise what the legacy tiles give
    rng = np.random.default_rng(13)
    n, h, w = 192, 80, 256
    chunk = jnp.asarray(rng.integers(0, 4096, (n, h, w)), jnp.float32)
    th = _pick_row_tile(h, w)
    tp = _pick_pair_tile(n // 2, th, w)

    def step(row_tile, pair_tile):
        state = (
            jnp.zeros((n // 2, h, w), jnp.float32),
            jnp.zeros((h, w), jnp.float32),
            jnp.zeros((h, w), jnp.float32),
        )
        return ops.ema_welford_step(
            *state, chunk, alpha=0.25, offset=4096.0, backend="pallas",
            row_tile=row_tile, pair_tile=pair_tile,
        )

    for a, b in zip(step(None, None), step(th, tp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("h,in_dtype,want", [
    (65, "float32", 65),   # no aligned divisor: the full height is the floor
    (72, "float32", 24),   # legacy 72 rows would be 2.25x the budget
    (80, "uint16", 16),    # legacy 80 rows: 2.3x; 16-row u16 tiles fit
])
def test_ema_pin_yields_where_legacy_pick_overruns(h, in_dtype, want):
    """At w=2048 the legacy pick's one-pair block overruns 2x the
    corrected budget; the corrected model then picks a legal tile."""
    th, tp = budget.resolve_tiles("ema", 1, h, 2048, in_dtype=in_dtype)
    assert (th, tp) == (want, 1)
    bb = budget.block_bytes("ema", th, tp, 2048, in_dtype=in_dtype)
    assert bb <= budget.VMEM_BUDGET or th == h


def test_heuristic_output_bit_identical_to_legacy_tiles():
    """Default (heuristic) geometry produces bit-identical output to the
    pre-PR pickers' explicit tiles on the pallas path."""
    rng = np.random.default_rng(11)
    frames = jnp.asarray(rng.integers(0, 4096, (3, 20, 16, 64)), jnp.float32)
    th = _pick_row_tile(16, 64)
    tp = _pick_pair_tile(10, th, 64)
    default = alg3_subtract_average(frames, interpret=True)
    legacy = alg3_subtract_average(
        frames, row_tile=th, pair_tile=tp, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(default), np.asarray(legacy))


# ---------------------------------------------------------------------------
# Plan resolution: modes, precedence, executors.
# ---------------------------------------------------------------------------


def test_heuristic_plan_is_default_and_empty():
    cfg = _cfg()
    assert cfg.tile_plan == "heuristic"
    plan = tune.resolve_plan(cfg)
    assert plan is tune.HEURISTIC_PLAN
    assert plan.tile_args("stream") == {
        "row_tile": None, "pair_tile": None, "placement": None
    }
    assert plan.num_slots is None


def test_config_rejects_bad_tile_plan():
    with pytest.raises(ValueError, match="tile_plan"):
        _cfg(tile_plan="")
    with pytest.raises(ValueError, match="tile_plan"):
        _cfg(tile_plan=123)


def test_explicit_tile_overrides_beat_plan(tmp_path):
    cfg = _cfg(row_tile=8, pair_tile=2, tile_plan="auto")
    den = StreamingDenoiser(cfg)
    assert den.filter.tile_args("stream") == {
        "row_tile": 8, "pair_tile": 2, "placement": None
    }


def test_auto_mode_tunes_caches_and_replays(tmp_path):
    cfg = _cfg(tile_plan="auto")
    plan = tune.resolve_plan(cfg)
    assert plan.source == "tuned"
    assert plan.num_slots in (1, 2, 3)
    assert plan.frames_per_chunk is not None
    cache_file = tmp_path / "plans.json"
    assert cache_file.exists()
    # same config re-resolves from the in-process memo (same object)
    assert tune.resolve_plan(cfg) is plan
    # a fresh process (memo cleared) replays the persistent cache
    tune.clear_plan_memo()
    replayed = tune.resolve_plan(cfg)
    assert replayed.source == "cache"
    assert replayed.num_slots == plan.num_slots


def test_cache_hit_performs_no_measurement(monkeypatch):
    from repro.tune import autotune

    cfg = _cfg(tile_plan="auto", backend="pallas")
    tune.resolve_plan(cfg)  # populate the persistent cache
    tune.clear_plan_memo()
    calls = []
    monkeypatch.setattr(
        autotune, "family_timer",
        lambda *a, **k: calls.append("tiles") or (lambda *t: 0.0),
    )
    monkeypatch.setattr(
        autotune, "tune_exec_knobs",
        lambda *a, **k: calls.append("exec") or {},
    )
    plan = tune.resolve_plan(cfg)
    assert plan.source == "cache"
    assert calls == []


def test_plan_resolution_happens_once_per_config(monkeypatch):
    from repro.tune import autotune

    count = [0]
    real = autotune.tune_plan

    def counting(config, cache=None):
        count[0] += 1
        return real(config, cache)

    monkeypatch.setattr(autotune, "tune_plan", counting)
    cfg = _cfg(tile_plan="auto")
    StreamingDenoiser(cfg)
    StreamingDenoiser(cfg)          # same config: memo, no re-tune
    StreamingDenoiser(_cfg(tile_plan="auto"))  # equal config: still memo
    assert count[0] == 1


def test_pipelined_applies_plan_ring_depth(tmp_path):
    """A pre-built plan file's executor knobs steer run_pipelined; the
    numeric stream is untouched (depth is scheduling-only)."""
    cfg = _cfg()
    path = tmp_path / "prebuilt.json"
    entries = {
        exec_key(
            "pair_average", cfg.num_groups, cfg.frames_per_group,
            cfg.height, cfg.width, backend="xla",
        ): {"num_slots": 4, "frames_per_chunk": cfg.frames_per_group},
    }
    path.write_text(json.dumps({"version": SCHEMA_VERSION, "entries": entries}))
    planned = _cfg(tile_plan=str(path))
    groups = _groups(cfg)
    out_ref, rep_ref = run_inline(cfg, iter(groups), prefetch=False)
    out, rep = run_pipelined(planned, iter(groups))
    assert rep.num_slots == 4
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_ref))
    # explicit argument still wins over the plan
    _, rep2 = run_pipelined(planned, iter(groups), num_slots=2)
    assert rep2.num_slots == 2
    # ...and so does a non-default config.num_slots (same explicit-
    # overrides-win precedence as row_tile/pair_tile)
    pinned = _cfg(tile_plan=str(path), num_slots=3)
    _, rep3 = run_pipelined(pinned, iter(groups))
    assert rep3.num_slots == 3


def test_plan_file_tiles_apply_and_stream_is_bit_identical(tmp_path):
    cfg = _cfg(backend="pallas")
    path = tmp_path / "prebuilt.json"
    entries = {
        family_key(
            "stream", cfg.pairs_per_group, cfg.height, cfg.width,
            in_dtype="uint16", acc_dtype="float32", backend="pallas",
        ): {"row_tile": 8, "pair_tile": 5},
    }
    path.write_text(json.dumps({"version": SCHEMA_VERSION, "entries": entries}))
    planned = _cfg(backend="pallas", tile_plan=str(path))
    den = StreamingDenoiser(planned)
    args = den.filter.tile_args("stream")
    assert (args["row_tile"], args["pair_tile"]) == (8, 5)
    groups = _groups(cfg)
    out_ref, _ = run_inline(cfg, iter(groups), prefetch=False)
    out, _ = run_inline(planned, iter(groups), prefetch=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_ref))


# ---------------------------------------------------------------------------
# Cache contract: malformed / stale / missing never crash a stream.
# ---------------------------------------------------------------------------


def test_malformed_cache_file_retunes_not_crashes(tmp_path):
    cache_file = tmp_path / "plans.json"
    cache_file.write_text('{"version": 1, "entries": {"truncated"')
    cfg = _cfg(tile_plan="auto")
    plan = tune.resolve_plan(cfg)   # re-tunes straight through the junk
    assert plan.source == "tuned"
    json.loads(cache_file.read_text())  # replaced by a valid store


def test_stale_schema_version_reads_as_empty(tmp_path):
    cache_file = tmp_path / "plans.json"
    cache_file.write_text(json.dumps({"version": 999, "entries": {"k": {}}}))
    cfg = _cfg(tile_plan="auto")
    assert tune.resolve_plan(cfg).source == "tuned"


def test_missing_plan_file_raises_at_resolve_time(tmp_path):
    planned = _cfg(tile_plan=str(tmp_path / "nope.json"))
    with pytest.raises(ValueError, match="does not exist"):
        tune.resolve_plan(planned)


def test_malformed_plan_file_falls_back_to_heuristic(tmp_path):
    path = tmp_path / "garbage.json"
    path.write_text("not json at all")
    planned = _cfg(tile_plan=str(path))
    with pytest.warns(RuntimeWarning, match="falling back to the heuristic"):
        plan = tune.resolve_plan(planned)
    assert plan.tile_args("stream") == {
        "row_tile": None, "pair_tile": None, "placement": None
    }
    # ...and the stream still runs, numerically identical to heuristic
    cfg = _cfg()
    groups = _groups(cfg)
    out_ref, _ = run_inline(cfg, iter(groups), prefetch=False)
    out, _ = run_inline(planned, iter(groups), prefetch=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_ref))


def test_corrupt_exec_knobs_degrade_to_config_defaults(tmp_path):
    """A stale/hand-edited executor-knob entry (negative or mistyped
    num_slots) must degrade to the config defaults, never reach
    RingBuffer()."""
    cfg = _cfg()
    path = tmp_path / "bad-exec.json"
    entries = {
        exec_key(
            "pair_average", cfg.num_groups, cfg.frames_per_group,
            cfg.height, cfg.width, backend="xla",
        ): {"num_slots": -2, "frames_per_chunk": "400"},
    }
    path.write_text(json.dumps({"version": SCHEMA_VERSION, "entries": entries}))
    planned = _cfg(tile_plan=str(path))
    plan = tune.resolve_plan(planned)
    assert plan.num_slots is None and plan.frames_per_chunk is None
    groups = _groups(cfg)
    out, rep = run_pipelined(planned, iter(groups))  # config default depth
    assert rep.num_slots == cfg.num_slots
    out_ref, _ = run_inline(cfg, iter(groups), prefetch=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_ref))


def test_stale_plan_entry_with_non_dividing_tiles_is_skipped(tmp_path):
    """A plan measured for another shape (tiles no longer divide) must be
    ignored, not crash the kernels."""
    cfg = _cfg(backend="pallas")
    path = tmp_path / "stale-shape.json"
    entries = {
        family_key(
            "stream", cfg.pairs_per_group, cfg.height, cfg.width,
            in_dtype="uint16", acc_dtype="float32", backend="pallas",
        ): {"row_tile": 7, "pair_tile": 3},  # divide neither H=16 nor P=10
    }
    path.write_text(json.dumps({"version": SCHEMA_VERSION, "entries": entries}))
    planned = _cfg(backend="pallas", tile_plan=str(path))
    plan = tune.resolve_plan(planned)
    assert plan.tile_args("stream") == {
        "row_tile": None, "pair_tile": None, "placement": None
    }
    groups = _groups(cfg)
    out, _ = run_inline(planned, iter(groups), prefetch=False)  # no crash
    out_ref, _ = run_inline(cfg, iter(groups), prefetch=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_ref))


# ---------------------------------------------------------------------------
# Static plans: the jitted step compiles exactly once per stream.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("filter_name,fn", [
    ("pair_average", lambda: ops.stream_step),
    ("ema_variance", lambda: ops.ema_welford_step),
])
def test_auto_stream_compiles_step_exactly_once(filter_name, fn):
    """Under tile_plan='auto' the resolved plan is a static argument: a
    full streaming run enters the jitted step cache exactly once (PR 3's
    retrace-guard discipline, now covering tuned plans)."""
    cfg = _cfg(tile_plan="auto", filter_name=filter_name, num_groups=5)
    tune.resolve_plan(cfg)  # tuning happens here, outside the counted run
    groups = _groups(cfg)
    den = StreamingDenoiser(cfg)
    jitted = fn()
    if not hasattr(jitted, "_cache_size"):  # pragma: no cover - newer jax
        pytest.skip("jax jit cache introspection not available")
    state = den.init()
    state = den.ingest(state, jnp.asarray(groups[0]), step=0)
    after_first = jitted._cache_size()
    for k, g in enumerate(groups[1:], start=1):
        state = den.ingest(state, jnp.asarray(g), step=k)
    jax.block_until_ready(den.finalize(state))
    assert jitted._cache_size() == after_first  # zero mid-stream retraces
    # a second identical stream re-enters the same single entry
    den2 = StreamingDenoiser(cfg)
    state = den2.init()
    for k, g in enumerate(groups):
        state = den2.ingest(state, jnp.asarray(g), step=k)
    jax.block_until_ready(den2.finalize(state))
    assert jitted._cache_size() == after_first


def test_auto_pipelined_matches_heuristic_bits_for_all_filters():
    """tile_plan='auto' changes scheduling/geometry only: every filter's
    pipelined output is bit-identical to the heuristic-plan run."""
    from repro.denoise import FILTERS

    for name in sorted(FILTERS):
        if name.startswith("_"):
            continue
        cfg_h = _cfg(filter_name=name)
        cfg_a = _cfg(filter_name=name, tile_plan="auto")
        groups = _groups(cfg_h, seed=7)
        out_h, _ = run_pipelined(cfg_h, iter(groups))
        out_a, _ = run_pipelined(cfg_a, iter(groups))
        np.testing.assert_array_equal(
            np.asarray(out_h), np.asarray(out_a), err_msg=name
        )
