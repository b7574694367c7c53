"""What the AR cell's readers share: the program's ``ar.*`` spans, which
``progspans`` (prefixes ``pocl.``, ``lbm.``) does not see, read from
``Reduced.host`` and clipped to the window; and the device time and
roofline share of the AR kernels, per frame (one ``bench.chain``, the
request name the pass-through readers read too). Where the trace has
none of them, each gives ``None``."""
from __future__ import annotations

import peaks
import progspans
import tracereduce

PREFIX = "ar."
REQUEST = "bench.chain"


def time_ns(red, names):
    """Summed time of the ``ar.*`` spans named in ``names``, clipped to
    the window; ``None`` where the trace has none of them."""
    spans = [(max(s, red.t0), min(e, red.t1)) for n, s, e in red.host
             if n.startswith(PREFIX) and n in names
             and e > red.t0 and s < red.t1]
    if not spans:
        return None
    return sum(e - s for s, e in spans)


def per_frame(red, ns):
    return progspans.per_request(red, REQUEST, ns)


def _kernel_ns(ctx, module):
    if ctx.trace is None:
        return None, 0
    evs = tracereduce.module_events(ctx.trace, module)
    return (sum(e - s for _, _, s, e in evs) if evs else None), len(evs)


def module_ms_per_frame(ctx, module):
    """Device time of ``module`` over the window's frames, in ms."""
    ns, _ = _kernel_ns(ctx, module)
    per = None if ns is None else per_frame(ctx.trace, ns)
    return None if per is None else per / 1e6


def roofline(ctx, module, bytes_per_slot: int):
    """The least time of the ``module`` calls of the window (each over
    the cell's ``capacity`` slots, ``bytes_per_slot`` each, at the
    chip's peak HBM bandwidth) over their device time, in %."""
    ns, calls = _kernel_ns(ctx, module)
    if ns is None:
        return None
    least_s = (bytes_per_slot * ctx.cell.config["capacity"] * calls
               / peaks.peak(ctx.device_kind, "hbm_bytes_per_s"))
    return 100.0 * least_s / (ns / 1e9)
