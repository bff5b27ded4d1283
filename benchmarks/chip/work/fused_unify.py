"""Required work of the downlink re-unification kernel call
(``fused_unify``): each client's task vectors -> bf16 unified vector,
packed masks and lambda sums."""

from __future__ import annotations


def required(clients: int, tasks: int, tasks_per_client: int, d: int):
    """(flops, bytes): reads the T*d fp32 task vectors once (not an
    N x K x d gather of them); writes N*d bf16 unified vectors and
    N*K*ceil(d/32) mask words.  About four operations per slot
    coordinate (sign sum, aligned max, mask, masked abs sums)."""
    n, t, k = clients, tasks, tasks_per_client
    w = -(-d // 32)
    nbytes = t * d * 4 + n * d * 2 + n * k * w * 4
    flops = 4 * n * k * d
    return flops, nbytes
