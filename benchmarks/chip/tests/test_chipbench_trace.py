"""The trace reduction on a small trace recorded on a TPU v5 lite: three
calls of one jitted matmul-and-tanh (each a copy-start, a copy-done and
a fusion on the device), each inside a ``bench.tiny_call`` host span,
2 ms apart.  Expected values are counted by hand from its events."""

import chipbench_testlib as lib

from benchlib import trace

TRACE = str(lib.CHIP / "tests" / "data" / "tiny.xplane.pb")


def test_busy_union_counts_each_instant_once():
    s = trace.reduce(TRACE)
    # per call: copy-start 13 + copy-done 2..3 + fusion; the third call's
    # copy-start ends where its copy-done starts, so they merge
    assert s.busy_ns == (13 + 3 + 15976) + (13 + 2 + 15778) + (16 + 15727)
    assert s.n_devices == 1


def test_per_op_time_by_kind():
    s = trace.reduce(TRACE)
    assert s.op_ns == {"fusion": 15976 + 15778 + 15727,
                       "copy-start": 39.0, "copy-done": 8.0}
    assert [len(s.op_events[k]) for k in ("fusion", "copy-start")] == [3, 3]
    assert s.kernel_calls("fusion") == 0      # a fusion is no custom call


def test_gaps_labelled_by_host_span():
    s = trace.reduce(TRACE)
    longest = sorted(s.gaps, key=lambda g: -g[1])[:3]
    assert longest == [("tiny_call", 49253730 - 45247177),
                       ("tiny_call", 52566093 - 49269525),
                       ("tiny_call", 54381409 - 52581838)]
    assert s.span_count == {"tiny_call": 3}


def test_window_and_breakdown():
    s = trace.reduce(TRACE)
    # no bench.window span: from the first device op to the last span end
    assert s.window == (45231182.0, 54381409.0)
    assert abs(s.idle_pct() - 100 * (1 - 47528 / (54381409 - 45231182))) < 1e-9
    b = s.breakdown(top=2)
    assert b["device_ops"][0] == ["fusion", 47481e-9]
    assert len(b["idle_gaps"]) == 2


def test_op_kind_strips_instance_number():
    name = ("%fusion.688 = bf16[4,1024,2048]{2,1,0} fusion(bf16[4] %x), "
            "kind=kLoop")
    assert trace.op_kind(name) == "fusion"
    assert trace.op_kind("%routed_matmul_pallas.82 = bf16[64,1,16] "
                         "custom-call(%a)") == "routed_matmul_pallas"
