"""round.masked_agg_roofline: required work of the Eq. 3-4 kernel
(``work/masked_agg.py``) per call, times its calls in the trace, over
the device time of its events, against the peaks."""

from benchlib import load
from benchlib.roofline import share_pct

KERNEL = "masked_agg_batched_packed"


def read(obs):
    t = obs.trace
    if t is None:
        return None
    if not t.kernel_calls(KERNEL):
        raise LookupError(f"no {KERNEL} kernel in the trace; kernels seen: "
                          f"{sorted(t.custom_calls)}")
    w = obs.work
    flops, nbytes = load("work", "masked_agg").required(
        w["clients"], w["tasks"], w["tasks_per_client"], w["d"])
    calls = t.kernel_calls(KERNEL)
    return share_pct(flops * calls, nbytes * calls, t.kernel_s(KERNEL),
                     obs.peaks)
