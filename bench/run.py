"""Time one cell of BENCHMARK.json on the chips of this machine.

    python bench/run.py --workload prism_u16.paced --seed 7 --seconds 30 --trace 0

Prints one JSON object as the last line of stdout: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared for ``correct``
beside its limit, which also end standard error. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the checkout root, not bench/, so that bench's modules import as bench.*
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench import cell as cell_mod, spec  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax

    # JAX's persistent compile cache: where JAX_COMPILATION_CACHE_DIR says,
    # else at a fixed path inside the checkout, so that only a cell's first
    # run there compiles
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result, _ = cell_mod.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), t_start=T_START
    )
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
