"""Find the knee of a paced cell: serve it at several camera counts in one
process and report, for each, the delivered rate against the offered one
and whether the lag grows over the window.

    python bench/sweep.py --workload prism_u16.paced --cameras 4,8,12,16 --seconds 10

The cell's traffic mix fixes its camera count; this tool is how that count
was chosen (about four fifths of the largest count that holds its rate
with no growing lag). For each count the scheduler is sized as the cell's
configuration sizes it for its own count: one slot per camera on one chip,
enough four-slot mesh executors for every camera on four. One JSON line
per count; nothing here is a benchmark result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench import cell as cell_mod, spec  # noqa: E402


def sized(cell: spec.Cell, cameras: int) -> spec.Cell:
    config = json.loads(json.dumps(cell.config))
    sched = config["scheduler"]
    if config.get("mesh_banks"):
        sched["max_executors"] = math.ceil(cameras / config["mesh_banks"])
    else:
        sched["slots_per_executor"] = cameras
    sched["max_waiting"] = 4 * cameras
    sched["max_sessions"] = 5 * cameras
    return dataclasses.replace(
        cell, config=config, traffic={**cell.traffic, "cameras": cameras}
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--cameras", required=True, help="comma-separated counts")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    base = spec.load_cell(args.workload)
    for cams in (int(c) for c in args.cameras.split(",")):
        cell = sized(base, cams)
        r, run = cell_mod.run_cell(cell, args.seed, args.seconds, False,
                                   t_start=time.perf_counter())
        acqs = sorted(run.acquisitions, key=lambda a: a.last_due)
        lat = [a.delivered - a.last_due for a in acqs if a.delivered is not None]
        third = max(1, len(lat) // 3)
        offered = cams * 1e6 / base.traffic["frame_interval_us"]
        print(json.dumps({
            "cameras": cams,
            "offered_frames_per_s": offered,
            "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            "delivered_share": r["metrics"]["frames_per_s"]["value"] / offered,
            "latency_first_third_ms": 1e3 * sorted(lat[:third])[third // 2] if lat else None,
            "latency_last_third_ms": 1e3 * sorted(lat[-third:])[third // 2] if lat else None,
            "attempted": r["attempted"], "failed": r["failed"], "correct": r["correct"],
        }), flush=True)


if __name__ == "__main__":
    main()
