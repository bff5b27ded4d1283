"""Required work of the Eq. 3-4 kernel call (``masked_agg``): every
member slot's masked, weighted contribution folded into its task."""

from __future__ import annotations


def required(clients: int, tasks: int, tasks_per_client: int, d: int):
    """(flops, bytes): reads N*d bf16 unified vectors and the N*K*ceil(d/32)
    mask words as uploaded (not a dense N x T scatter of them); writes
    T*d fp32 tau_hat and the T*d agreement numerators at one byte.
    Two flops (multiply-add) per member coordinate for Eq. 4 and one
    for the Eq. 3 sign sum."""
    n, t, k = clients, tasks, tasks_per_client
    w = -(-d // 32)
    nbytes = n * d * 2 + n * k * w * 4 + t * d * 4 + t * d
    flops = 3 * n * k * d
    return flops, nbytes
