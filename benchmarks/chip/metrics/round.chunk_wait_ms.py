"""round.chunk_wait_ms: host milliseconds per round that
``round_chunked`` blocks on each chunk's merge before refilling its
staging buffers: total time of the program spans ``repro.round.wait``
over the count of ``repro.round``."""

from benchlib import program_spans


def read(obs):
    spans = program_spans.of(obs)
    if spans is None:
        return None
    return spans.ms_per("round.wait", "round")
