"""Backend compiles (persistent-cache loads included) inside the
measured window: each is a stall on the serving loop."""


def read(run):
    return len(run.compiles)
