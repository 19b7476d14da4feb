"""Share of the decision kernel's roofline: the least time its rounds
could take on the chip (operations over peak compute or bytes over HBM
bandwidth, whichever is larger: the memory bound binds at these shapes)
over their device time in the trace."""

from bench.readout import decision_kernel_cost, roofline_share
from bench.trace import device_time

KERNEL = "decision_stats_pallas"   # the pallas_call's op in the trace


def read(run):
    if run.trace is None:
        return None
    calls, secs = device_time(run.trace, KERNEL)
    cfg = run.cfg
    flops, byts = decision_kernel_cost(cfg["slots"], cfg["model"]["n_classes"],
                                       run.r_step)
    share = roofline_share(calls, flops, byts, secs, run.peaks)
    return None if share is None else share[0]
