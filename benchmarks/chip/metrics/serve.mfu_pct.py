"""serve.mfu_pct: the whole generate program's share of the chip's peak:
required work of each batch (``work/decode.py``, counted for the
batch's real requests; the larger of its compute and memory bounds),
summed over the batches of the window, over the window's length."""

from benchlib import load
from benchlib.roofline import share_pct


def read(obs):
    w = obs.work
    if not w.get("batch_requests"):
        return None
    decode = load("work", "decode")
    flops = nbytes = 0
    for n in w["batch_requests"]:
        f, b = decode.required(w["config"], n, w["prompt_len"], w["new_tokens"])
        flops, nbytes = flops + f, nbytes + b
    return share_pct(flops, nbytes, w["elapsed_s"], obs.peaks)
