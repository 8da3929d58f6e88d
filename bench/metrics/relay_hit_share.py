"""Share of the window's completed rank requests served from relayed
psi (HBM or DRAM hit), in percent: the trigger and affinity router
decide it."""


def read(run):
    if not run.hits:
        return None
    return 100.0 * sum(h != "miss" for h in run.hits) / len(run.hits)
