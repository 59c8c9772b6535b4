"""Benchmark harness: one module per paper table. Prints
``name,us_per_call,derived`` CSV. ``--full`` uses paper-scale N=1000;
``--list`` prints the registry; an unknown ``--only`` raises a
``ValueError`` listing the valid module names (the repo's
dispatch-validation convention)."""

from __future__ import annotations

import argparse
import sys
import time
import traceback
from typing import Sequence

from benchmarks import (
    fig8_denoise_snr,
    roofline_report,
    table1_kernel_latency,
    table2_loop_breakdown,
    table3_throughput,
    table4_led_trigger,
    table5_multibank,
    table6_group_sweep,
    table7_cpu_baseline,
    table8_buffered_vs_inline,
    table9_ring_depth,
    table10_filter_zoo,
    table11_multitenant,
    table12_autotune,
    table13_bandwidth,
    table14_fleet,
    table15_observability,
    table16_slo,
    table17_autoscale,
)
from repro import compile_cache

MODULES = [
    ("table1", table1_kernel_latency),
    ("table2", table2_loop_breakdown),
    ("table3", table3_throughput),
    ("table4", table4_led_trigger),
    ("table5", table5_multibank),
    ("table6", table6_group_sweep),
    ("table7", table7_cpu_baseline),
    ("table8-10", table8_buffered_vs_inline),
    ("table9", table9_ring_depth),
    ("table10-zoo", table10_filter_zoo),
    ("table11-multitenant", table11_multitenant),
    ("table12-autotune", table12_autotune),
    ("table13-bandwidth", table13_bandwidth),
    ("table14-fleet", table14_fleet),
    ("table15-observability", table15_observability),
    ("table16-slo", table16_slo),
    ("table17-autoscale", table17_autoscale),
    ("fig8", fig8_denoise_snr),
    ("roofline", roofline_report),
]


def select(only: str | None) -> list:
    """Modules whose registry name contains ``only`` (all when None).

    Raises ``ValueError`` listing the valid names when nothing matches —
    same contract as the ``ops``/filter dispatch errors, so a typo'd
    ``--only`` fails loudly instead of silently running nothing.
    """
    if only is None:
        return MODULES
    picked = [(name, mod) for name, mod in MODULES if only in name]
    if not picked:
        names = tuple(name for name, _ in MODULES)
        raise ValueError(
            f"--only must match one of {names}, got {only!r}"
        )
    return picked


def main(argv: Sequence[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale N=1000")
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--list",
        action="store_true",
        help="print the registered module names and exit",
    )
    args = ap.parse_args(argv)
    if args.list:
        for name, mod in MODULES:
            doc = (mod.__doc__ or "").strip()
            print(name, "-", doc.splitlines()[0] if doc else "(no description)")
        return
    picked = select(args.only)
    compile_cache.enable()
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in picked:
        t0 = time.time()
        try:
            mod.run(quick=not args.full)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name},-1,EXCEPTION")
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
