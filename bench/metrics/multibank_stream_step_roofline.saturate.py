"""Kernels: the Pallas ``multibank_stream_step`` kernel's share of its HBM
roofline, in %, over the traced window.

Bytes are the least any implementation must move for the groups the kernel
folded (``bench.layers.min_hbm_bytes``: each group's wire input read once,
1/G of the float32 output written once); each kernel event gives its banks
and output shape, ``f32[B, N/2, H, W]``, in its HLO text. Time is the
kernel events' device time inside the window. The share is those bytes at
the chip's peak HBM bandwidth (``peaks.json``) over that time. A
streamed step also reads and writes its float32 running sum, which this
count leaves out on purpose: moving it is the kernel's cost, not the work.
"""

import re

from bench.layers import WIRE_BYTES, min_hbm_bytes

KERNEL = "multibank_stream_step"
_OUT = re.compile(r"=\s*f32\[(\d+),(\d+),(\d+),(\d+)\]")


def read(run):
    t = run.trace
    if t is None or not run.peak:
        return None
    cfg = run.denoise
    moved = seconds = 0.0
    for op in t.ops:
        if not op.short.startswith(KERNEL):
            continue
        m = _OUT.search(op.name)
        if m is None:
            continue
        banks, half, h, w = (int(x) for x in m.groups())
        per_event = min_hbm_bytes(banks, groups=cfg.num_groups, frames_per_group=2 * half,
                                  height=h, width=w, in_bytes=WIRE_BYTES[cfg.stream_dtype])
        moved += op.count * per_event
        seconds += op.seconds
    if seconds <= 0:
        return None
    return 100.0 * moved / run.peak["hbm_bytes_per_s"] / seconds
