"""serve.lora_kernel_roofline: required work of the modulated LoRA
contractions (``work/decode.py`` ``lora_required``, for each batch's
real requests) of the batches in the trace, over the device time of
the LoRA kernels that ran (``routed_matmul`` on the dense-routed path,
``modulated_matmul`` on the fused one)."""

from benchlib import load
from benchlib.roofline import share_pct

KERNELS = ("routed_matmul", "modulated_matmul")


def read(obs):
    t, w = obs.trace, obs.work
    if t is None or not w.get("batch_requests"):
        return None
    if not t.kernel_calls(*KERNELS):
        raise LookupError(f"no {KERNELS} kernel in the trace; kernels seen: "
                          f"{sorted(t.custom_calls)}")
    decode = load("work", "decode")
    flops = nbytes = 0
    for n in w["batch_requests"]:
        f, b = decode.lora_required(w["config"], n, w["prompt_len"],
                                    w["new_tokens"])
        flops, nbytes = flops + f, nbytes + b
    return share_pct(flops, nbytes, t.kernel_s(*KERNELS), obs.peaks)
