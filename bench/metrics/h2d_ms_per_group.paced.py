"""Acquire/stage: the program's ``serve.h2d_s`` (a chunk's host->device
landing, its pull from the camera excluded) per group, in ms (see
bench.stages)."""

from bench.stages import per_group_ms


def read(run):
    return per_group_ms(run, "serve.h2d_s")
