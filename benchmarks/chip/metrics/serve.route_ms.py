"""serve.route_ms: host milliseconds per decode batch in
``route_batch`` (LRU lookups, rebuilds of the misses, stacking the
per-request adapters): total time of the program spans
``repro.serve.route`` over the count of ``repro.serve.generate``."""

from benchlib import program_spans


def read(obs):
    spans = program_spans.of(obs)
    if spans is None:
        return None
    return spans.ms_per("serve.route", "serve.generate")
