"""finetune.mfu_pct: model FLOP/s utilisation of the fine-tune window:
required operations per step (``work/train_step.py``; recomputation not
counted) times steps per second, over the chip's peak bf16 FLOP/s."""

from benchlib import load


def read(obs):
    w = obs.work
    if not w.get("steps"):
        return None
    flops, _ = load("work", "train_step").required(w["config"], w["batch"],
                                                   w["seq"])
    return 100.0 * flops * w["steps"] / w["elapsed_s"] / obs.peaks["bf16_flops_per_s"]
