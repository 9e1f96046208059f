"""The MLA decode kernel's share (%) of its roofline: the least bytes it
must read (each live cache entry once per row, step and layer: the
engine's ``engine.count.latent_tokens`` counts of the traced stretch's own
windows) over peak HBM bandwidth, or its operations over peak FLOP/s where
larger, against the device time of the kernel's events in the same trace
(``kernels/ops.py`` names its call ``_mla_decode_pallas``)."""

from bench import counts, flops_mla, trace

KERNEL = r"_mla_decode_pallas"


def read(run):
    if not run.events:
        return None
    t = trace.kernel_ns(run.events, KERNEL) * 1e-9
    entries = counts.total(run.events, "engine.count.latent_tokens")
    if not t or not entries:
        return None
    share, _ = trace.roofline_share(
        flops_mla.latent_flops(run.model, entries),
        flops_mla.latent_bytes(run.model, entries), t,
        run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"])
    return share
