"""The reduction from a profiler trace to busy time, device operations and
named idle gaps, on a small hand-made XSpace."""

import pathlib
from types import SimpleNamespace

import pytest

from bench import xplane

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "two_chips.xplane.txt"


@pytest.fixture(scope="module")
def data():
    return xplane.load(str(FIXTURE))


def test_one_chip_busy_ops_and_gaps(data):
    s = xplane.reduce_trace(data, chips=1)
    # the window is the 10 us bench.window span
    assert s.window_s == pytest.approx(10e-6)
    # XLA Ops of TPU:0 clipped to the window: 0.5 + 1.0 + 0.5 + 0.5 us;
    # the XLA Modules line is not counted
    assert s.busy_by_chip == [pytest.approx(2.5e-6)]
    assert s.busy_s == pytest.approx(2.5e-6)
    # ops are named program/instruction: the program from the XLA Modules
    # event around the op, else from its hlo_module stat
    ops = dict((k, v) for k, v in s.device_ops())
    assert ops == {
        "jit_step/multibank_stream_step.1": pytest.approx(1.5e-6),
        "jit_step/copy.1": pytest.approx(1.0e-6),
    }
    # gaps: [0.5, 2.5] us lies under serve.cohort, [4.0, 9.5] us under
    # serve.coalesce; spans of other names never name a gap
    assert [(n, pytest.approx(t)) for n, t in s.idle_gaps()] == [
        ("serve.coalesce", 5.5e-6),
        ("serve.cohort", 2.0e-6),
    ]


def test_busy_is_the_mean_over_the_cells_chips(data):
    s = xplane.reduce_trace(data, chips=2)
    assert s.busy_by_chip == [pytest.approx(2.5e-6), pytest.approx(8.0e-6)]
    assert s.busy_s == pytest.approx(5.25e-6)
    # TPU:1's op lies outside every span: its gaps are named no_span
    assert [n for n, _ in s.idle_gaps()].count("no_span") == 2


def test_kernel_roofline_counts_least_bytes_over_kernel_time(data):
    from bench import spec

    readers = spec.per_layer_readers(spec.load_cell("prism_u16.saturate"))
    read = readers["multibank_stream_step_roofline.saturate"]
    s = xplane.reduce_trace(data, chips=1)
    denoise = SimpleNamespace(num_groups=8, stream_dtype="u16")
    run = SimpleNamespace(trace=s, denoise=denoise, peak={"hbm_bytes_per_s": 819e9})
    # two events of f32[2, 8, 16, 128]: one whole, one half inside the window;
    # each moves at least 2 banks x (16 frames x 16 x 128 x 2 B in + 1/8 of
    # the 8 x 16 x 128 x 4 B out), in 1.5 us of kernel time
    least = 1.5 * 2 * (16 * 16 * 128 * 2 + 8 * 16 * 128 * 4 / 8)
    assert read(run) == pytest.approx(100 * least / 819e9 / 1.5e-6)
    # no trace, or no kernel event in it: nothing to read
    assert read(SimpleNamespace(trace=None, denoise=denoise, peak=run.peak)) is None
    s.ops = [op for op in s.ops if "multibank" not in op.name]
    assert read(run) is None


def test_binary_xplane_reads_the_same(tmp_path, data):
    from jax.profiler import ProfileData

    pb = tmp_path / "plugins" / "profile" / "run" / "host.xplane.pb"
    pb.parent.mkdir(parents=True)
    pb.write_bytes(ProfileData.text_proto_to_serialized_xspace(FIXTURE.read_text()))
    found = xplane.find_xplane(str(tmp_path))
    assert found == str(pb)
    a = xplane.reduce_trace(xplane.load(found), chips=1)
    b = xplane.reduce_trace(data, chips=1)
    assert (a.busy_s, a.device_ops(), a.idle_gaps()) == (b.busy_s, b.device_ops(), b.idle_gaps())


def test_a_trace_without_the_window_span_is_refused(tmp_path):
    text = FIXTURE.read_text().replace('"bench.window"', '"bench.other"')
    path = tmp_path / "t.txt"
    path.write_text(text)
    with pytest.raises(ValueError, match="bench.window"):
        xplane.reduce_trace(xplane.load(str(path)), chips=1)
