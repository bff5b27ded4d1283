"""The fine-tune runner at a tiny size on the CPU: a sound run is
correct; the fp8 control and each fault a training step can have are
not."""

import pytest

import chipbench_testlib as lib


@pytest.fixture(scope="module")
def conf():
    return lib.tiny_lm("qwen2.5-3b")


def tiny(conf):
    tf = lib.traffic("finetune", batch=2, seq=64, task_region=64)
    return lib.cell("qwen2.5-3b.finetune", conf, tf)


def _patch_step(monkeypatch, wrap):
    import repro.train.trainer as trainer
    orig = trainer.make_train_step

    def make(model, opt=None, grad_clip=1.0):
        step, o = orig(model, opt, grad_clip)
        return wrap(step), o
    monkeypatch.setattr(trainer, "make_train_step", make)


def test_sound_run_is_correct(conf):
    out = lib.run_cell(tiny(conf), seconds=1.0)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["finetune_tokens_per_s"]["value"] > 0


def test_control_fails_a_limit(conf):
    c = tiny(conf)
    readings = lib.control(c)
    limits = c.traffic["limits"]
    assert any(readings[k] > limits[k] for k in limits), (readings, limits)


def test_unchanged_state_is_caught(conf, monkeypatch):
    def wrap(step):
        def same(params, lora, state, batch):
            _, _, m = step(params, lora, state, batch)
            return lora, state, m
        return same
    _patch_step(monkeypatch, wrap)
    out = lib.run_cell(tiny(conf), seconds=0.5)
    assert out["correct"] is False


def test_half_batch_is_caught(conf, monkeypatch):
    def wrap(step):
        def half(params, lora, state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(params, lora, state,
                        {k: v[:n] for k, v in batch.items()})
        return half
    _patch_step(monkeypatch, wrap)
    out = lib.run_cell(tiny(conf), seconds=0.5)
    assert out["correct"] is False


def test_altered_loss_is_caught(conf, monkeypatch):
    def wrap(step):
        def altered(params, lora, state, batch):
            lora, state, m = step(params, lora, state, batch)
            return lora, state, {"loss": m["loss"] * 1.01}
        return altered
    _patch_step(monkeypatch, wrap)
    out = lib.run_cell(tiny(conf), seconds=0.5)
    assert out["correct"] is False
