"""The per-layer metrics that read program spans (``repro.*``): each on
synthetic spans, and each from a traced run of its cell's runner at a
tiny size on the CPU, whose trace carries the program's spans on its
host plane; and how ``benchlib.program_spans`` finds a run's trace,
sums its spans and labels its gaps."""

import time

import jax
import jax.numpy as jnp
import pytest

import chipbench_testlib as lib
from benchlib import load, manifest, program_spans
from benchlib.context import Context, Observations
from benchlib.program_spans import ProgramSpan, ProgramSpans
from benchlib.trace import TraceSummary, capture, reduce, span
from test_chipbench_round import tiny as tiny_round
from test_chipbench_serve import tiny as tiny_serve

PROGRAM = {"round": ProgramSpan(4, 4e9, 1e8),
           "round.pack": ProgramSpan(16, 8e8, 2e8),
           "round.assemble": ProgramSpan(16, 1.6e9, 1.6e9),
           "round.wait": ProgramSpan(16, 2e8, 2e8),
           "serve.generate": ProgramSpan(5, 1e10, 1e6),
           "serve.route": ProgramSpan(5, 3e9, 1e9),
           "serve.rebuild": ProgramSpan(100, 2e9, 2e9)}

# metric -> its reading of PROGRAM, in ms
WANT = {"round.pack_ms": 800 / 4, "round.assemble_ms": 1600 / 4,
        "round.chunk_wait_ms": 200 / 4, "serve.route_ms": 3000 / 5,
        "serve.rebuild_ms": 2000 / 100}

WINDOW = (0.0, 1e10)


def program_span_metrics():
    return [m["name"] for m in manifest.load()["per_layer"]
            if m["source"] == "program_span"]


def test_every_program_span_metric_is_covered():
    assert sorted(program_span_metrics()) == sorted(WANT)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reads_a_synthetic_summary(name, monkeypatch):
    reader = load("metrics", name)
    obs = Observations(peaks=lib.PEAKS,
                       trace=TraceSummary(WINDOW, 1, 5e9, {}))
    monkeypatch.setattr(program_spans, "of",
                        lambda o: ProgramSpans(WINDOW, PROGRAM))
    assert reader.read(obs) == pytest.approx(WANT[name], rel=1e-12)
    # silent without its spans, as on a program that opens none
    monkeypatch.setattr(program_spans, "of",
                        lambda o: ProgramSpans(WINDOW, {}))
    assert reader.read(obs) is None
    monkeypatch.undo()
    assert reader.read(Observations(peaks=lib.PEAKS)) is None


def _traced(c, tmp_path):
    ctx = Context(c, lib.SEED, 0.5, True, jax.devices(), lib.PEAKS,
                  time.perf_counter(), str(tmp_path / c.name))
    return load("runners", c.traffic["runner"]).run(ctx)


@pytest.mark.parametrize("workload", ["qwen2-0.5b.round_sync",
                                      "qwen2-0.5b.round_population",
                                      "qwen2-0.5b.serve_zipf"])
def test_traced_cpu_run_reads_every_program_span_metric(workload, tmp_path,
                                                        monkeypatch):
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path)
    if workload.endswith("serve_zipf"):
        # an LRU smaller than the task mix, so that the window misses
        c = tiny_serve(lib.tiny_lm("qwen2-0.5b"), lru_capacity=2)
    else:
        c = tiny_round(workload)
    obs = _traced(c, tmp_path)
    names = [m["name"] for m in c.per_layer if m["source"] == "program_span"]
    assert names
    for name in names:
        value = load("metrics", name).read(obs)
        assert value is not None and value > 0, name
    t = program_spans.of(obs)
    root = "serve.generate" if workload.endswith("serve_zipf") else "round"
    # the window's rounds or batches, each one root span
    assert t.count(root) == obs.work.get("rounds",
                                         len(obs.work.get("batch_requests",
                                                          [])))
    for child in t.spans.values():
        assert 0 <= child.self_ns <= child.total_ns


def _record(trace_dir, calls: int) -> TraceSummary:
    """A small CPU trace the way a runner traces its window: ``calls``
    jitted calls, each inside ``repro.tiny`` inside ``bench.call``."""
    from repro.common.trace import span as program_span
    step = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    step(x).block_until_ready()
    with capture(str(trace_dir)):
        for _ in range(calls):
            with span("call"), program_span("tiny"):
                step(x).block_until_ready()
    return reduce(str(trace_dir))


def test_of_reads_the_trace_of_its_window(tmp_path, monkeypatch):
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path)
    two = _record(tmp_path / "two", 2)
    three = _record(tmp_path / "three", 3)      # the newer trace
    assert program_spans.of(Observations(trace=two)).count("tiny") == 2
    assert program_spans.of(Observations(trace=three)).count("tiny") == 3
    # a window that no trace there has, and an untraced run, read None
    other = TraceSummary((1.0, 2.0), 1, 0.0, {})
    assert program_spans.of(Observations(trace=other)) is None
    assert program_spans.of(Observations()) is None


def test_self_time_leaves_out_direct_children_on_one_thread():
    threads = [[(0, 100, "a"), (10, 30, "a.b"), (40, 90, "a.c"),
                (50, 60, "a.c.d"), (2000, 2100, "a")],
               [(5, 50, "x")]]               # another thread: no parent
    got = program_spans._sum(threads, 0.0, 1000.0)   # the 2nd "a" is late
    assert {k: (v.count, v.total_ns, v.self_ns) for k, v in got.items()} == {
        "a": (1, 100, 100 - 20 - 50), "a.b": (1, 20, 20),
        "a.c": (1, 50, 40), "a.c.d": (1, 10, 10), "x": (1, 45, 45)}


def test_gap_label_names_the_innermost_program_span():
    label = program_spans._label
    bench = [(0, 100, "round")]
    program = [(0, 100, "round"), (10, 50, "round.pack"),
               (60, 100, "round.assemble")]
    assert label(20, 40, bench, program) == "round/round.pack"
    assert label(52, 58, bench, program) == "round/round"
    # across two children the parent overlaps the gap most
    assert label(45, 65, bench, program) == "round/round"
    # a tie of overlaps goes to the shorter span, in either order
    spans = [(0, 100, "outer"), (5, 30, "inner")]
    assert label(10, 20, [], spans) == "-/inner"
    assert label(10, 20, [], spans[::-1]) == "-/inner"
    assert label(200, 300, bench, program) == "-"
    assert label(20, 40, bench, []) == "round"
