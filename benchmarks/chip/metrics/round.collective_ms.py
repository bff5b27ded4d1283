"""round.collective_ms: device milliseconds per round that a chip's op
stream spends in cross-chip collectives (``all-reduce``,
``all-gather``, ``reduce-scatter``, ``collective-permute``,
``all-to-all`` ops), mean over the chips, over the rounds of the traced
window.  Two programs run them in a sharded round: the round itself
(its two psums, ``all-reduce``) and the slices of its padded outputs
back to d (``collective-permute``, and an ``all-gather`` for the mask
words' uneven last shard).  An async collective shows as its
``-start`` and ``-done`` ops: their durations are the time the stream
spends starting it and waiting for it, the part not hidden behind other
work, not the transfer time."""

KINDS = ("all-reduce", "all-gather", "reduce-scatter",
         "collective-permute", "all-to-all")


def read(obs):
    t = obs.trace
    rounds = obs.work.get("rounds")
    if t is None or not rounds or not t.n_devices:
        return None
    ns = sum(v for k, v in t.op_ns.items() if k.startswith(KINDS))
    return ns * 1e-6 / t.n_devices / rounds
