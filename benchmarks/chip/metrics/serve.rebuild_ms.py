"""serve.rebuild_ms: host milliseconds per LRU miss of the modulator
store, rebuilding one task's adapter from the packed state: total time
of the program spans ``repro.serve.rebuild`` over their own count."""

from benchlib import program_spans


def read(obs):
    spans = program_spans.of(obs)
    if spans is None:
        return None
    return spans.ms_per("serve.rebuild", "serve.rebuild")
