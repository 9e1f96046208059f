"""Weights of a latent-attention MoE decoder (DeepSeek-V2), made on the
device from the run's seed.

The tree has the layout the served model reads: ``embed`` (table and
untied head), ``lead`` (the leading dense-MLP layers, one subtree each),
``blocks`` (the MoE layers, one stacked leaf per weight: attention ``wq``,
``wkv_a``, ``kv_norm``, ``wkv_b``, ``wo``; the router over all
``num_experts``; the ``experts_held`` experts this chip holds, as
``[layers, experts, in, out]`` stacks; the shared expert) and
``final_norm``. Built from the configuration's sizes alone, so the plain
reference reads the very same numbers without importing the program. One
jitted call makes every leaf, in the dtype the configuration serves, on
the default device (``bench/weights.py`` does the making).

Norm scales are random around one, so a path that drops a scale shows in
the comparison.
"""

from __future__ import annotations

from bench import weights as _dense


def layout(m: dict) -> dict:
    """``{path: (shape, init)}`` for model sizes ``m`` (the configuration's
    ``model`` group), as ``bench/weights.py``'s ``layout``."""
    d, V, H = m["d_model"], m["vocab_size"], m["num_heads"]
    r, dn, dr, dv = (m["kv_lora_rank"], m["qk_nope_dim"], m["qk_rope_dim"],
                     m["v_head_dim"])
    lead = m.get("first_dense", 0)
    R = m["num_layers"] - lead
    E, e, f = m["num_experts"], m["experts_held"], m["moe_d_ff"]
    out: dict = {("embed", "tokens"): ((V, d), ("normal", d ** -0.5)),
                 ("embed", "head", "kernel"): ((d, V), ("normal", d ** -0.5)),
                 ("final_norm", "scale"): ((d,), ("scale", 0.1))}

    def linear(path, lead_shape, d_in, d_out):
        out[path + ("kernel",)] = (lead_shape + (d_in, d_out),
                                   ("normal", d_in ** -0.5))

    def attention(prefix, lead_shape):
        out[prefix + ("norm", "scale")] = (lead_shape + (d,), ("scale", 0.1))
        a = prefix + ("attn",)
        linear(a + ("wq",), lead_shape, d, H * (dn + dr))
        linear(a + ("wkv_a",), lead_shape, d, r + dr)
        out[a + ("kv_norm", "scale")] = (lead_shape + (r,), ("scale", 0.1))
        linear(a + ("wkv_b",), lead_shape, r, H * (dn + dv))
        linear(a + ("wo",), lead_shape, H * dv, d)
        out[prefix + ("mlp_norm", "scale")] = (lead_shape + (d,),
                                               ("scale", 0.1))

    def swiglu(prefix, lead_shape, width):
        linear(prefix + ("w_gate",), lead_shape, d, width)
        linear(prefix + ("w_up",), lead_shape, d, width)
        linear(prefix + ("w_down",), lead_shape, width, d)

    for i in range(lead):
        attention(("lead", str(i)), ())
        swiglu(("lead", str(i), "mlp"), (), m["d_ff"])
    blk = ("blocks", "0")
    attention(blk, (R,))
    mlp = blk + ("mlp",)
    linear(mlp + ("router",), (R,), d, E)
    out[mlp + ("w_gate",)] = ((R, e, d, f), ("normal", d ** -0.5))
    out[mlp + ("w_up",)] = ((R, e, d, f), ("normal", d ** -0.5))
    out[mlp + ("w_down",)] = ((R, e, f, d), ("normal", f ** -0.5))
    swiglu(mlp + ("shared",), (R,), m["shared_d_ff"])
    return out


def make(m: dict, seed: int, dtype: str = "float32") -> dict:
    """Every leaf of the tree for sizes ``m`` from ``seed``, in ``dtype``."""
    spec = tuple((p, s, i) for p, (s, i) in sorted(layout(m).items()))
    return _dense._make(spec, _dense.seed_key(seed), dtype)


def param_count(m: dict, matmul_only: bool = False) -> int:
    """Number of weights on this chip; with ``matmul_only``, those that
    multiply an activation (the 2-D kernels, the held experts' stacks and
    the output head). Every held expert counts, as the chip holds them;
    model FLOPs of routed experts count only routed pairs
    (``bench/flops_mla.py``)."""
    n = 0
    for path, (shape, _) in layout(m).items():
        size = 1
        for s in shape:
            size *= s
        if not matmul_only or path[-1] in ("kernel", "w_gate", "w_up",
                                           "w_down"):
            n += size
    return n
