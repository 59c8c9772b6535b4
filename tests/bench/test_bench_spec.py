"""BENCHMARK.json and the files it names: every cell finds its
configuration, traffic mix and metric readers by name, a new cell or metric
needs only new files, and the byte arithmetic of the roofline."""

import json
import math
import re
import shutil

import pytest

from bench import cell as cell_mod, layers, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(spec.ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_every_cell_loads_with_its_files(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"] in (1, 4)
        assert cell.config["denoise"]["num_groups"] >= 1
        assert {"cameras", "frame_interval_us", "pool_groups", "check_share"} <= set(cell.traffic)
        readers = spec.per_layer_readers(cell)
        assert set(readers) == {m["name"] for m in cell.per_layer}
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in reported, (w["name"], m["name"])


def test_names_units_and_keys_follow_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({x["name"] for x in bench[k]}) == len(bench[k])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers_named = {m["layer"] for m in bench["per_layer"]}
    assert all(layer and "\n" not in layer for layer in layers_named)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for c in bench["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert all(NAME.match(k) for k in c["reduced"])
    # the whole of a later check fits its time: 24 cells at run_seconds
    t = bench["run_seconds"]
    assert (2 + 14 * 24) * (t + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_a_new_cell_and_metric_need_only_new_files(tmp_path, bench):
    root = tmp_path / "checkout"
    shutil.copytree(spec.ROOT / "bench", root / "bench")
    # new files: a configuration, a traffic mix and a metric reader
    conf = json.loads((spec.ROOT / "bench/configs/prism_u16.json").read_text())
    conf["denoise"]["stream_dtype"] = "p12"
    (root / "bench/configs/prism_mono12p.json").write_text(json.dumps(conf))
    (root / "bench/traffic/bursty.json").write_text(json.dumps(
        {"cameras": 2, "frame_interval_us": 200.0, "lead_in_s": 1.0, "pool_groups": 4,
         "check_share": 0.5}))
    (root / "bench/metrics/cameras_seen.bursty.py").write_text(
        "def read(run):\n    return float(len({a.camera for a in run.acquisitions})) or None\n")
    before = {p.relative_to(root): p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file() and "fixtures" not in p.parts}
    spec_ = dict(bench)
    spec_["configs"] = bench["configs"] + [
        {"name": "prism_mono12p", "source": "https://arxiv.org/abs/2508.14917",
         "file": "bench/configs/prism_mono12p.json", "reduced": [], "why": "packed wire"}]
    spec_["workloads"] = bench["workloads"] + [
        {"name": "prism_mono12p.bursty", "config": "prism_mono12p", "traffic": "bursty",
         "chips": 1, "why": "new"}]
    spec_["per_layer"] = bench["per_layer"] + [
        {"name": "cameras_seen.bursty", "unit": "cameras", "better": "higher",
         "source": "program_counter", "layer": "load generator", "moves": "frames_per_s",
         "workloads": ["prism_mono12p.bursty"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(spec_))

    cell = spec.load_cell("prism_mono12p.bursty", root=root)
    assert cell.config["denoise"]["stream_dtype"] == "p12"
    assert cell.traffic["frame_interval_us"] == 200.0
    assert [m["name"] for m in cell.per_layer] == ["cameras_seen.bursty"]
    readers = spec.per_layer_readers(cell, root=root)

    class _Acq:
        def __init__(self, c):
            self.camera = c

    run = cell_mod.Run(paced=True, window=(0.0, 1.0), acquisitions=[_Acq(0), _Acq(1)],
                       registry=None, snapshots=({}, {}), trace=None, denoise=None, peak={})
    assert readers["cameras_seen.bursty"](run) == 2.0
    # no file the harness already had was touched
    after = {p.relative_to(root): p.read_bytes() for p in (root / "bench").rglob("*")
             if p.is_file() and p.relative_to(root) in before}
    assert after == before


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell("prism_u16.nope")


def test_paper_group_is_40_96_mb_of_input():
    # one group of one camera: 1000 frames of 80 x 256 mono12-in-u16
    one = layers.min_hbm_bytes(1, groups=8, frames_per_group=1000, height=80, width=256)
    inputs = 1000 * 80 * 256 * 2
    assert inputs == 40_960_000
    # the least traffic adds 1/G of the (N/2, H, W) float32 output
    assert one == inputs + 500 * 80 * 256 * 4 / 8
    assert layers.min_hbm_bytes(4, groups=8, frames_per_group=1000, height=80,
                                width=256) == 4 * one


def test_peaks_table_names_its_source_and_the_v5e_row():
    peaks = json.loads((spec.ROOT / "bench" / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes"] == 16e9


def test_nearest_rank():
    v = list(range(1, 201))
    assert cell_mod.nearest_rank(v, 95) == 190
    assert cell_mod.nearest_rank(v, 50) == 100
    assert cell_mod.nearest_rank([3.0], 95) == 3.0
    assert math.isclose(cell_mod.nearest_rank([2.0, 1.0], 50), 1.0)
