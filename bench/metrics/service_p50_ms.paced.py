"""Serve: median of the program's ``serve.latency_s`` (a group staged ->
its cohort step done), in ms, over the groups of the window's
acquisitions."""

from bench.cell import nearest_rank


def read(run):
    names = {a.name for a in run.acquisitions}
    samples = [
        v
        for inst in run.registry.instruments()
        if inst.name == "serve.latency_s" and dict(inst.label_key).get("session") in names
        for v in inst._merged()[0]
    ]
    return nearest_rank(samples, 50) * 1e3 if samples else None
