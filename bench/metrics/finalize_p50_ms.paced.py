"""Serve: median of the program's ``serve.finalize_s`` (an acquisition's
slot extract and finalize, until the output exists on the device), in ms,
over the window's delivered acquisitions (see bench.stages)."""

from bench.stages import p50_ms


def read(run):
    return p50_ms(run, "serve.finalize_s")
