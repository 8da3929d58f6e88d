"""Real (unpadded) requests per rank launch in the window: how much the
micro-batcher groups."""

from bench.lib.readings import mean


def read(run):
    return mean([len(r.lens) for r in run.launches if r.span == "rank"])
