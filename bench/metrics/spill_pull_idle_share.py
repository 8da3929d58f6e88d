"""Share of the window's deferred DRAM-spill pulls that settled while
the event loop had nothing due, in percent: 100 x ``pulls_settled_idle``
/ ``pulls_deferred`` of the page pools' ``h2d`` ledger.  None where
the program keeps no such counter or deferred no pull."""


def read(run):
    if run.program is None:
        return None
    h2d = run.program.h2d
    deferred = h2d.get("pulls_deferred", 0)
    if deferred <= 0:
        return None
    return 100.0 * h2d.get("pulls_settled_idle", 0) / deferred
