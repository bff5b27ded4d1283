"""The serving runner at a tiny size on the CPU: a sound run is
correct; the fp8 control and an altered served token are not."""

import numpy as np
import pytest

import chipbench_testlib as lib


@pytest.fixture(scope="module")
def conf():
    return lib.tiny_lm("qwen2-0.5b")


def tiny(conf, **kw):
    tf = lib.traffic("serve_zipf", batch=4, prompt_len=16, new_tokens=8,
                     tasks=6, pool_batches=3, **kw)
    return lib.cell("qwen2-0.5b.serve_zipf", conf, tf)


def test_sound_run_is_correct(conf):
    out = lib.run_cell(tiny(conf), seconds=1.0)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0
    m = out["metrics"]
    assert m["serve_tokens_per_s"]["value"] > 0
    assert out["attempted"] % 4 == 0 and out["attempted"] >= 4


def test_control_fails_the_limit():
    # the fp8 control's gap grows with depth and width: at 2 layers of 64
    # it stays under the limit set from the cell's readings on the chip
    c = tiny(lib.tiny_lm("qwen2-0.5b", d_model=256, d_ff=512, n_layers=6,
                         vocab=4096))
    readings = lib.control(c)
    limit = c.traffic["limits"]["served_logit_gap"]
    assert readings["served_logit_gap"] > limit, readings
    assert readings["program_served_logit_gap"] <= limit, readings


def test_altered_token_is_caught(conf, monkeypatch):
    from repro.serve import MultiTenantDecoder
    orig = MultiTenantDecoder.generate

    def generate(self, prompts, task_ids, **kw):
        out = np.asarray(orig(self, prompts, task_ids, **kw)).copy()
        out[:, -3] = (out[:, -3] + 1) % conf["vocab_size"]
        return out
    monkeypatch.setattr(MultiTenantDecoder, "generate", generate)
    out = lib.run_cell(tiny(conf), seconds=0.5)
    assert out["correct"] is False
