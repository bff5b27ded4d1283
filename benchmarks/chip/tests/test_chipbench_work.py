"""Each work function against a hand count at a small shape, and the
peaks table."""

import pytest

import chipbench_testlib as lib

from benchlib import device, load
from benchlib.roofline import least_seconds, share_pct

SMALL = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
         "num_key_value_heads": 1, "num_hidden_layers": 3, "vocab_size": 10,
         "lora": {"rank": 2}}


def test_round_step_counts_wire_buffers_only():
    # N=4, T=3, K=2, d=40 -> 2 words per mask row
    flops, nbytes = load("work", "round_step").required(4, 3, 2, 40)
    uploads = 4 * 40 * 2 + 4 * 2 * 2 * 4
    assert nbytes == uploads + 3 * 40 * 4 + uploads
    assert flops == 6 * 4 * 2 * 40


def test_masked_agg_reads_slot_words_not_dense_scatter():
    flops, nbytes = load("work", "masked_agg").required(4, 3, 2, 40)
    # N*K words (not N*T): a dense scatter would be 4*3*2*4 = 96 bytes
    assert nbytes == 4 * 40 * 2 + 4 * 2 * 2 * 4 + 3 * 40 * 4 + 3 * 40
    assert flops == 3 * 4 * 2 * 40


def test_fused_unify_reads_task_vectors_once():
    flops, nbytes = load("work", "fused_unify").required(4, 3, 2, 40)
    # T*d fp32 task vectors, not the N*K*d gather (4*2*40*4 = 1280)
    assert nbytes == 3 * 40 * 4 + 4 * 40 * 2 + 4 * 2 * 2 * 4
    assert flops == 4 * 4 * 2 * 40


def test_qwen2_shapes():
    s = load("work", "qwen2_shapes")
    # per layer: q 8x8, k and v 8x4 each, o 8x8, gate/up/down 8x16 each
    per_layer = 64 + 2 * 32 + 64 + 3 * 128
    assert s.matmul_params(SMALL) == 3 * per_layer + 10 * 8
    # LoRA rank 2 on q (8->8), o (8->8), down (16->8)
    assert s.lora_params(SMALL) == 3 * 2 * ((8 + 8) + (8 + 8) + (16 + 8))


def test_train_step_counts_forward_and_backward_once():
    s = load("work", "qwen2_shapes")
    flops, nbytes = load("work", "train_step").required(SMALL, 2, 5)
    attn = 2 * 3 * 2 * 5 * 5 * 2 * 4
    assert flops == (10 * (4 * s.matmul_params(SMALL)
                           + 6 * s.lora_params(SMALL)) + 3 * attn)
    assert nbytes == 2 * 2 * s.matmul_params(SMALL)


def test_decode_counts_weights_once_per_step():
    s = load("work", "qwen2_shapes")
    flops, nbytes = load("work", "decode").required(SMALL, 2, 4, 3)
    w = 2 * s.matmul_params(SMALL)
    adapters = 2 * 2 * s.lora_params(SMALL)
    kv = 3 * 2 * 1 * 4 * 2                      # layers * (k, v) * kv heads * hd * 2 B
    ctx = 4 + (4 + 1) + (4 + 2)                 # prompt, then each decode step's context
    assert nbytes == 3 * (w + adapters) + 2 * kv * ctx
    per_tok = 2 * (s.matmul_params(SMALL) + s.lora_params(SMALL))
    assert flops == (2 * per_tok * (4 + 2) + 2 * 3 * 2 * 16 * 2 * 4
                     + 2 * 3 * 4 * 2 * 4 * 11)
    lflops, lbytes = load("work", "decode").lora_required(SMALL, 2, 4, 3)
    assert lbytes == 3 * adapters
    assert lflops == 2 * 2 * s.lora_params(SMALL) * 6


def test_roofline_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert least_seconds(50, 30, peaks) == 3.0
    assert least_seconds(500, 30, peaks) == 5.0
    assert share_pct(500, 30, 10.0, peaks) == 50.0
    assert share_pct(1, 1, 0.0, peaks) is None


def test_peaks_keyed_by_device_kind():
    p = device.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]


def test_missing_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no entry in peaks.json"):
        device.peaks_for("TPU v9 imaginary")


def test_serve_shares_count_real_requests_only():
    from benchlib.context import Observations
    from benchlib.trace import TraceSummary
    conf = lib.config("qwen2-0.5b")
    trace = TraceSummary((0.0, 1e9), 1, 5e8, {"routed_matmul_pallas": 1e8},
                         {"routed_matmul_pallas": [1e8]},
                         custom_calls={"routed_matmul_pallas"})

    def reads(batch_requests):
        obs = Observations(peaks=lib.PEAKS, trace=trace, work={
            "batch_requests": batch_requests, "elapsed_s": 10.0,
            "prompt_len": 256, "new_tokens": 128, "config": conf})
        return (load("metrics", "serve.mfu_pct").read(obs),
                load("metrics", "serve.lora_kernel_roofline").read(obs))

    full, lora_full = reads([64, 64])
    part, lora_part = reads([64, 1])
    assert part < full and lora_part < lora_full
    # the LoRA work is linear in the requests: a padded row adds none
    assert lora_part == pytest.approx(lora_full * (64 + 1) / 128, rel=1e-9)


def test_kernel_reader_raises_when_its_kernel_is_missing():
    from benchlib.context import Observations
    from benchlib.trace import TraceSummary
    trace = TraceSummary((0.0, 1e9), 1, 5e8, {"fusion": 1e8},
                         {"fusion": [1e8]}, custom_calls=set())
    obs = Observations(peaks=lib.PEAKS, trace=trace, work={
        "clients": 4, "tasks": 3, "tasks_per_client": 2, "d": 40})
    for name in ("round.masked_agg_roofline", "round.fused_unify_roofline"):
        with pytest.raises(LookupError, match="kernels seen"):
            load("metrics", name).read(obs)
    assert load("metrics", "round.masked_agg_roofline").read(
        Observations(peaks=lib.PEAKS)) is None
