"""Acquire/stage: the program's ``serve.backpressure_s`` (acquisition
threads blocked on a full staging ring, waiting for the executor) per
group, in ms (see bench.stages)."""

from bench.stages import per_group_ms


def read(run):
    return per_group_ms(run, "serve.backpressure_s")
