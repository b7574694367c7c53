"""Device time per call of the CFD kernel: the summed durations of the
device's module events of ``lbm_step``, over their number, in ms."""
import tracereduce

MODULE = "jit_lbm_step"


def read(ctx):
    if ctx.trace is None:
        return None
    evs = tracereduce.module_events(ctx.trace, MODULE)
    if not evs:
        return None
    return sum(e - s for _, _, s, e in evs) / len(evs) / 1e6
