"""round.assemble_ms: host milliseconds per round in
``_assemble_downlinks``, the round's host edge on the way out (slicing
the batched downlinks per client): total time of the program spans
``repro.round.assemble`` over the count of ``repro.round``."""

from benchlib import program_spans


def read(obs):
    spans = program_spans.of(obs)
    if spans is None:
        return None
    return spans.ms_per("round.assemble", "round")
