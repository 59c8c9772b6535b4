"""Serve: median of the program's ``serve.coalesce_s`` (the batching wait
of the cohort that folded a group, 0 where there was none), in ms, over
the groups of the window's delivered acquisitions (see bench.stages)."""

from bench.stages import p50_ms


def read(run):
    return p50_ms(run, "serve.coalesce_s")
