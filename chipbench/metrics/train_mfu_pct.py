"""The whole profiled job's share of the chip's bf16 peak: the model's
operations per token (forward and backward, no recomputation; from the
configuration's shapes, ``model_flops_per_token`` of its reference) times
the window's tokens per second, over the peak of ``peaks.json``.
Default-precision float32 matmuls run on the MXU at that rate."""


def read(raw):
    rate = raw["e2e"].get("train_tokens_per_s")
    flops = raw.get("model_flops_per_token")
    if not rate or not flops:
        return None
    return 100.0 * flops * rate / raw["peaks"]["bf16_flops_per_s"]
