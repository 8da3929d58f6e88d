"""Model FLOPs of every prefill and rank launch in the window over the
launches' summed host time times the chip's peak, in percent: the
whole launch path's share of the peak, kernels and host work alike."""


def read(run):
    if run.peaks is None:
        return None
    done = [r for r in run.launches if r.span in ("prefill", "rank")]
    wall = sum(r.t1 - r.t0 for r in done)
    if not done or wall <= 0:
        return None
    work = sum(run.work(r)[0] for r in done)
    return 100.0 * work / (wall * run.peaks["flops_per_s"])
