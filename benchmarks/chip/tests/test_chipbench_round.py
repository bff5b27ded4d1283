"""The round runner at a tiny size on the CPU: a sound run is correct,
the control (the reference in bf16 in the program's place) and each
fault the round can have come out not correct."""

import dataclasses

import numpy as np
import pytest

import chipbench_testlib as lib


def tiny(workload="qwen2-0.5b.round_sync", d=8192, **kw):
    c = lib.cell(workload, lib.config("qwen2-0.5b", lora_d=d),
                 lib.traffic(workload.rsplit(".", 1)[1], **kw))
    if c.traffic.get("chunk_clients"):
        c.traffic.update(clients=16, chunk_clients=4)
    else:
        c.traffic.update(clients=8)
    c.traffic.update(tasks=6)
    return c


@pytest.mark.parametrize("workload", ["qwen2-0.5b.round_sync",
                                      "qwen2-0.5b.round_population"])
def test_sound_run_is_correct(workload):
    out = lib.run_cell(tiny(workload), seconds=0.5)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1
    assert list(out)[-1] == "checks"
    assert out["metrics"]["round_clients_per_s"]["value"] > 0


def test_control_fails_a_limit():
    # the cell's own N, T and K at a small d: the bf16 control's gaps
    # grow with the clients and tasks a round merges
    c = tiny(d=16384)
    c.traffic.update(clients=32, tasks=30)
    readings = lib.control(c)
    limits = c.traffic["limits"]
    assert any(readings[k] > limits[k] for k in limits), (readings, limits)


def _patch_round(monkeypatch, alter):
    from repro.core.server import MaTUServer
    orig = MaTUServer.round

    def round_(self, uploads, **kw):
        return alter(orig(self, uploads, **kw), uploads)
    monkeypatch.setattr(MaTUServer, "round", round_)


def test_altered_answer_is_caught(monkeypatch):
    def alter(downs, uploads):
        first = min(downs)
        dl = downs[first]
        u = np.asarray(dl.unified, np.float32).copy()
        u[: u.size // 64] *= -1.0
        downs[first] = dataclasses.replace(dl, unified=u.astype(dl.unified.dtype))
        return downs
    _patch_round(monkeypatch, alter)
    out = lib.run_cell(tiny(), seconds=0.3)
    assert out["correct"] is False


def test_half_the_clients_left_out_is_caught(monkeypatch):
    from repro.core.server import MaTUServer
    orig = MaTUServer.round

    def round_(self, uploads, **kw):
        return orig(self, uploads[: len(uploads) // 2], **kw)
    monkeypatch.setattr(MaTUServer, "round", round_)
    out = lib.run_cell(tiny(), seconds=0.3)
    assert out["correct"] is False


def test_stale_answer_is_caught(monkeypatch):
    cache = {}

    def alter(downs, uploads):
        return cache.setdefault("first", downs)
    _patch_round(monkeypatch, alter)
    out = lib.run_cell(tiny(check_rounds=4), seconds=0.5)
    assert out["correct"] is False
