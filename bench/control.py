"""Read the control of ``correct`` at a cell's own size: the plain
reference computed in bfloat16, put in the program's place, against the
float32 reference, over acquisitions drawn from several seeds.

    python bench/control.py --workload prism_u16.paced --seeds 11,12,13

One JSON line per seed with the largest ``max_abs_err`` the control reads
over ``--acquisitions`` acquisitions; the benchmark's limit on that number
has to sit below the smallest of them. The benchmark's own runs do not run
this. It needs a TPU, as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench import reference, spec  # noqa: E402
from bench.pool import acquisition_groups, make_pool  # noqa: E402


def control_errors(pool, *, groups: int, offset: float, seed: int, acquisitions: int):
    """``max_abs_err`` of the bfloat16 control against the float32
    reference, one per acquisition of camera 0."""
    import jax.numpy as jnp

    diffs = {}
    errs = []
    for k in range(acquisitions):
        idx = [int(i) for i in acquisition_groups(seed, 0, k, groups, len(pool))]
        for i in idx:
            if i not in diffs:
                diffs[i] = reference.diffs(pool[i], offset)
        ref = reference.pair_average([diffs[i] for i in idx])
        ctl = reference.pair_average_bf16(jnp.asarray(pool[idx]), offset)
        errs.append(reference.max_abs_err(ctl, ref))
    return errs


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--acquisitions", type=int, default=4)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit(f"control: needs a TPU, found {jax.devices()[0].platform!r}")
    cell = spec.load_cell(args.workload)
    d = cell.config["denoise"]
    for seed in (int(s) for s in args.seeds.split(",")):
        pool = make_pool(seed, cell.traffic["pool_groups"], d["frames_per_group"],
                         d["height"], d["width"])
        errs = control_errors(pool, groups=d["num_groups"], offset=d["offset"], seed=seed,
                              acquisitions=args.acquisitions)
        print(json.dumps({"seed": seed, "control_max_abs_err": max(errs),
                          "per_acquisition": errs}), flush=True)


if __name__ == "__main__":
    main()
