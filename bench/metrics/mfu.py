"""Whole-step share (%) of the chips' peak: model FLOPs of the prompts
prefilled and tokens generated by the requests completed in the window,
over window x chips x peak bf16 FLOP/s."""

from bench import flops


def read(run):
    done = [r for r in run.requests
            if r.ok and r.done is not None and r.done <= run.window_s]
    if not done:
        return None
    work = sum(flops.request_flops(run.model, run.matmul_params,
                                   r.prompt_len, r.out_tokens)
               for r in done)
    return 100.0 * work / (run.window_s * run.chips
                           * run.peaks["bf16_flops_per_s"])
