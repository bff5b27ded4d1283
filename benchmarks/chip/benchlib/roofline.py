"""Roofline arithmetic shared by the per-layer readers."""

from __future__ import annotations


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def share_pct(flops: float, nbytes: float, seconds: float, peaks: dict):
    """Roofline share in %, or None when nothing was timed."""
    if not seconds or seconds <= 0:
        return None
    return 100.0 * least_seconds(flops, nbytes, peaks) / seconds
