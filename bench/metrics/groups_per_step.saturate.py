"""Serve: groups folded per device step (cohort size) inside the window,
from the program's ``serve.latency_s`` sample counts and ``cohort_steps``."""


def read(run):
    s0, s1 = run.snapshots
    steps = s1["cohort_steps"] - s0["cohort_steps"]
    return (s1["groups"] - s0["groups"]) / steps if steps else None
