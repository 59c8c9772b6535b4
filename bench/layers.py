"""Arithmetic shared by per-layer metric readers that differ only in the
cells that report them (``<metric>.paced`` / ``<metric>.saturate``)."""

from __future__ import annotations

__all__ = ["transfer_ms_per_group", "device_idle_share", "min_hbm_bytes", "WIRE_BYTES"]

#: wire bytes per pixel of each ingest format (``repro.kernels.quant``)
WIRE_BYTES = {"u16": 2, "u8": 1, "p12": 1.5}


def transfer_ms_per_group(run):
    """Acquire/stage: host->device time per group, in ms.

    The program's ``serve.transfer_s`` times its acquisition thread from
    the pull of a chunk to the chunk landing on the device, so it includes
    the wait for the camera; the feed times that wait on its side
    (``source_wait_s``) and it is taken out here.
    """
    total, groups = 0.0, 0
    for a in run.acquisitions:
        if a.delivered is None:
            continue
        total += run.registry.value("serve.transfer_s", session=a.name) - a.source_wait_s
        groups += len(a.groups)
    return total / groups * 1e3 if groups else None


def device_idle_share(run):
    """Device: % of the traced window in which no operation ran, mean over
    the cell's chips."""
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def min_hbm_bytes(banks: int, *, groups: int, frames_per_group: int, height: int,
                  width: int, in_bytes: float = 2, accum_bytes: int = 4) -> float:
    """The least HBM traffic of folding one group per bank.

    Copied from ``repro.core.latency_model.hbm_traffic_bytes`` (Alg 3,
    ``total``): a whole acquisition must read its input once and write its
    output once, whatever implements it. One group's share is its input
    plus 1/G of the output.
    """
    frame = height * width
    inputs = frames_per_group * frame * in_bytes
    out = (frames_per_group // 2) * frame * accum_bytes
    return banks * (inputs + out / groups)
