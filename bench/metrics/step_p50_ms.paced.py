"""Serve: median of the program's ``serve.step_s`` (a group's cohort, from
the fetch from the rings to the step done on the device), in ms, over the
groups of the window's delivered acquisitions (see bench.stages)."""

from bench.stages import p50_ms


def read(run):
    return p50_ms(run, "serve.step_s")
