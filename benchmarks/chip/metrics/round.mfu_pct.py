"""round.mfu_pct: the whole round's share of the chip's peak: the
round's required bytes (``work/round_step.py``) times rounds per second
of the traced window, over the peak HBM bandwidth (the round is
bandwidth bound; ``benchlib.roofline`` takes the larger bound)."""

from benchlib import load
from benchlib.roofline import share_pct


def read(obs):
    w = obs.work
    if not w.get("rounds"):
        return None
    flops, nbytes = load("work", "round_step").required(
        w["clients"], w["tasks"], w["tasks_per_client"], w["d"])
    return share_pct(flops * w["rounds"], nbytes * w["rounds"],
                     w["elapsed_s"], obs.peaks)
