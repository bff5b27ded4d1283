"""round.pack_ms: host milliseconds per round in ``pack_uploads``, the
round's host edge on the way in: total time of the program spans
``repro.round.pack`` (one per round, or one per chunk of a chunked
round) over the count of ``repro.round``."""

from benchlib import program_spans


def read(obs):
    spans = program_spans.of(obs)
    if spans is None:
        return None
    return spans.ms_per("round.pack", "round")
