"""The per-layer metrics that read the serve layer's stage instruments and
spans: registry readers on a synthetic run, the executor's busy share on a
hand-made profile, and nothing read from a program that lacks them."""

import pathlib
from types import SimpleNamespace

import pytest

from bench import cell as cell_mod, spec, stages, xplane
from repro import obs

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "executor_lines.xplane.txt"

NEW = {
    "prism_u16.paced": ["h2d_ms_per_group.paced", "coalesce_p50_ms.paced",
                        "step_p50_ms.paced", "finalize_p50_ms.paced"],
    "prism_u16.saturate": ["h2d_ms_per_group.saturate", "backpressure_ms_per_group.saturate",
                           "executor_busy_share.saturate"],
}


def _readers(cell):
    return spec.per_layer_readers(spec.load_cell(cell))


def _acq(name, groups=8, delivered=1.0):
    return SimpleNamespace(name=name, groups=list(range(groups)), delivered=delivered)


def _run(registry, acquisitions, trace=None):
    return cell_mod.Run(paced=True, window=(0.0, 1.0), acquisitions=acquisitions,
                        registry=registry, snapshots=({}, {}), trace=trace,
                        denoise=None, peak={})


@pytest.fixture
def registry():
    """Two delivered acquisitions of the window, one still out, and one
    from outside the window that only the registry knows."""
    reg = obs.MetricsRegistry()
    for name, h2d, bp in (("c0k1", 0.08, 0.0), ("c1k1", 0.088, 0.016), ("c2k1", 1.0, 1.0),
                          ("c3k0", 5.0, 5.0)):
        reg.counter("serve.h2d_s", session=name).inc(h2d)
        reg.counter("serve.backpressure_s", session=name).inc(bp)
    for name, base in (("c0k1", 0.0), ("c1k1", 0.010), ("c2k1", 1.0), ("c3k0", 2.0)):
        for g in range(8):
            reg.histogram("serve.coalesce_s", session=name).observe(base + g * 1e-3)
            reg.histogram("serve.step_s", session=name).observe(base + 0.005 + g * 1e-3)
        reg.histogram("serve.finalize_s", session=name).observe(base + 0.002)
    return reg


@pytest.fixture
def window():
    return [_acq("c0k1"), _acq("c1k1"), _acq("c2k1", delivered=None)]


def test_every_new_metric_is_a_reader_of_its_cells():
    for cell, names in NEW.items():
        readers = _readers(cell)
        assert set(names) <= set(readers), cell


@pytest.mark.parametrize("metric,expected", [
    # (80 + 88) ms over 16 groups; c2k1 is not delivered, c3k0 not in the window
    ("h2d_ms_per_group.paced", (80 + 88) / 16),
    ("h2d_ms_per_group.saturate", (80 + 88) / 16),
    ("backpressure_ms_per_group.saturate", 16 / 16),
])
def test_counter_readers_sum_the_window_per_group(registry, window, metric, expected):
    cell = "prism_u16.paced" if metric.endswith(".paced") else "prism_u16.saturate"
    read = _readers(cell)[metric]
    assert read(_run(registry, window)) == pytest.approx(expected)


@pytest.mark.parametrize("metric,expected", [
    # 16 samples, nearest rank 8: c0k1's largest coalesce wait, 7 ms
    ("coalesce_p50_ms.paced", 7.0),
    ("step_p50_ms.paced", 12.0),
    # 2 samples, nearest rank 1: c0k1's 2 ms
    ("finalize_p50_ms.paced", 2.0),
])
def test_histogram_readers_take_the_window_median(registry, window, metric, expected):
    read = _readers("prism_u16.paced")[metric]
    assert read(_run(registry, window)) == pytest.approx(expected)


def test_registry_readers_read_nothing_from_a_program_without_the_instruments(window):
    # the parent program: serve.transfer_s and serve.latency_s only
    reg = obs.MetricsRegistry()
    for a in window:
        reg.counter("serve.transfer_s", session=a.name).inc(0.1)
        reg.histogram("serve.latency_s", session=a.name).observe(0.02)
    run = _run(reg, window)
    for cell, names in NEW.items():
        readers = _readers(cell)
        for name in names:
            assert readers[name](run) is None, name


def test_executor_busy_share_on_a_hand_made_profile():
    data = xplane.load(str(FIXTURE))
    # ex A busy 40%, ex B 50%; the acquisition thread holds no serve.wait
    assert stages.executor_busy_share(data) == pytest.approx(45.0)


def test_executor_busy_share_without_executor_lines_is_none(tmp_path):
    text = FIXTURE.read_text().replace('"serve.wait"', '"serve.cohort"')
    path = tmp_path / "parent.xplane.txt"
    path.write_text(text)
    assert stages.executor_busy_share(xplane.load(str(path))) is None


def test_executor_busy_share_reader_loads_the_runs_profile(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    pb = tmp_path / "plugins" / "profile" / "run" / "host.xplane.pb"
    pb.parent.mkdir(parents=True)
    pb.write_bytes(ProfileData.text_proto_to_serialized_xspace(FIXTURE.read_text()))
    monkeypatch.setattr(cell_mod, "TRACE_DIR", tmp_path)
    read = _readers("prism_u16.saturate")["executor_busy_share.saturate"]
    assert read(_run(None, [], trace=object())) == pytest.approx(45.0)
    # an untraced run has no profile to read
    assert read(_run(None, [], trace=None)) is None
