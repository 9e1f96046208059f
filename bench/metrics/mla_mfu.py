"""This chip's share (%) of its peak for a latent-attention MoE model:
model FLOPs of the chip's share (``bench/flops_mla.py``: routed experts by
their expected pairs on held experts) of the prompts prefilled past the
reused prefix and the tokens generated, for the requests completed in the
window, over window x chips x peak bf16 FLOP/s."""

from bench import flops_mla


def read(run):
    if "kv_lora_rank" not in run.model:
        return None
    done = [r for r in run.requests
            if r.ok and r.done is not None and r.done <= run.window_s]
    if not done:
        return None
    work = sum(flops_mla.request_flops(run.model, r.shared, r.prompt_len,
                                       r.out_tokens) for r in done)
    return 100.0 * work / (run.window_s * run.chips
                           * run.peaks["bf16_flops_per_s"])
