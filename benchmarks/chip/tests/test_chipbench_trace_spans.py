"""The trace reduction and the program-span reading
(``benchlib.program_spans``) on a small trace recorded on a TPU v5
lite by ``record_tiny_spans.py``: two calls of one jitted
matmul-and-tanh, each inside a ``bench.tiny_call`` span holding
``repro.tiny.outer`` > (``prep``, ``dispatch``, ``wait``), and a
``repro.tiny.before`` span ahead of everything.  Expected values are
counted by hand from its events (ns):

    repro.tiny.before   49407175 .. 51262515
    bench.tiny_call     51338055 .. 56681775 | 56705385 .. 62139924
    repro.tiny.outer    51349145 .. 55597295 | 56717865 .. 61061464
    repro.tiny.prep     51357665 .. 53460225 | 56733545 .. 58930724
    repro.tiny.dispatch 53466705 .. 53834865 | 58947204 .. 59302604
    repro.tiny.wait     53840125 .. 54343395 | 59309415 .. 59993504
    device ops          52288621 .. 52289328 | 57756210 .. 57756918

(The device clock reads about 1.2 ms behind the host's here, so each
call's ops fall inside its ``prep`` span.)"""

import chipbench_testlib as lib

from benchlib import program_spans, trace

TRACE = str(lib.CHIP / "tests" / "data" / "tiny_spans.xplane.pb")
# no bench.window: first bench span to its last end
WINDOW = (51338055.0, 62139924.0)


def test_program_spans_do_not_move_the_window():
    s = trace.reduce(TRACE)
    # the earlier repro.tiny.before leaves the window where it is
    assert s.window == WINDOW
    assert s.busy_ns == (13 + 2 + 689) + (13 + 3 + 690)
    assert s.span_count == {"tiny_call": 2}


def test_program_totals_and_self_times():
    s = program_spans.summarize(TRACE, WINDOW)
    outer = (55597295 - 51349145) + (61061464 - 56717865)
    prep = (53460225 - 51357665) + (58930724 - 56733545)
    dispatch = (53834865 - 53466705) + (59302604 - 58947204)
    wait = (54343395 - 53840125) + (59993504 - 59309415)
    got = {k: (v.count, v.total_ns, v.self_ns) for k, v in s.spans.items()}
    # tiny.before starts before the window: not counted
    assert got == {"tiny.outer": (2, outer, outer - prep - dispatch - wait),
                   "tiny.prep": (2, prep, prep),
                   "tiny.dispatch": (2, dispatch, dispatch),
                   "tiny.wait": (2, wait, wait)}
    assert s.ms("tiny.wait") == wait * 1e-6
    assert s.count("tiny.outer") == 2
    assert s.ms("tiny.before") == s.count("tiny.before") == 0
    assert s.ms_per("tiny.prep", "tiny.outer") == prep * 1e-6 / 2
    assert s.ms_per("tiny.prep", "tiny.before") is None


def test_gaps_labelled_by_innermost_program_span():
    g = program_spans.gaps(TRACE, WINDOW)
    longest = [tuple(gap) for gap in g["idle_gaps"]]
    # each long gap overlaps tiny.outer more than any child span
    assert longest[:3] == [("tiny_call/tiny.outer", 57756210 - 52289328),
                           ("tiny_call/tiny.outer", 62139924 - 57756918),
                           ("tiny_call/tiny.outer", 52288621 - 51338055)]
    # the 1-2 ns gaps between a call's ops lie inside prep and outer
    # alike: the tie goes to the shorter, prep
    assert {label for label, _ in longest[3:]} == {"tiny_call/tiny.prep"}
    assert len(longest) == 7
    # the same gaps as the reduction's, which labels by bench span only
    s = trace.reduce(TRACE)
    assert sorted(ns for _, ns in s.gaps) == sorted(ns for _, ns in longest)
    assert g["idle_ns"] == (WINDOW[1] - WINDOW[0]) - s.busy_ns
    # not under tiny.outer: the window's start before the first call's
    # outer, and each call's tail after it
    assert g["uncovered_ns"] == ((51349145 - 51338055)
                                 + (56717865 - 55597295)
                                 + (62139924 - 61061464))
