"""Required bytes of one MaTU server round at its wire layout: what the
round has to read and write at least, whatever layout an
implementation uses inside (no dense N x T mask scatter, no N x K x d
gather of task vectors).  The round is bound by memory bandwidth; its
arithmetic is a few operations per uploaded coordinate."""

from __future__ import annotations


def words(d: int) -> int:
    return -(-d // 32)


def required(clients: int, tasks: int, tasks_per_client: int, d: int):
    """(flops, bytes) of one round:

    * uploads in: N*d bf16 unified vectors + N*K*ceil(d/32) uint32 mask
      words (lambdas and sizes are negligible);
    * task vectors out: T*d fp32;
    * downlinks out: N*d bf16 + N*K*ceil(d/32) words.
    """
    n, t, k = clients, tasks, tasks_per_client
    wire = n * d * 2 + n * k * words(d) * 4
    nbytes = wire + t * d * 4 + wire
    flops = 2 * n * k * d + 4 * n * k * d
    return flops, nbytes
