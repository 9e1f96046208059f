"""Flash-decode kernels' share (%) of their roofline: the least bytes they
must read (each live K/V entry once) for the decode steps of the requests
completed in the window, over peak HBM bandwidth, against the device time
of the kernels' events in the trace (``kernels/ops.py`` names their calls
``_decode_pallas`` and ``_paged_decode_pallas``). Both count: the flat one
(fused windows, over the pool's flat view) and the paged one (K=1)."""

from bench import flops, trace

KERNELS = r"^%?_(?:paged_)?decode_pallas\b"


def read(run):
    if not run.events:
        return None
    t = trace.kernel_ns(run.events, KERNELS) * 1e-9
    if not t:
        return None
    done = [r for r in run.requests
            if r.ok and r.done is not None and r.done <= run.window_s]
    lo_hi = [(r.prompt_len, r.prompt_len + r.out_tokens - 1) for r in done]
    nbytes = sum(flops.decode_attn_bytes(run.model, a, b) for a, b in lo_hi)
    fl = sum(flops.decode_attn_flops(run.model, a, b) for a, b in lo_hi)
    # The trace covers the window; scale the window's work to the traced
    # span so the two measure the same stretch of time.
    scale = run.trace_window_s / run.window_s
    share, _ = trace.roofline_share(fl * scale, nbytes * scale, t,
                                    run.peaks["bf16_flops_per_s"],
                                    run.peaks["hbm_bytes_per_s"])
    return share
