"""Distributed building blocks on a small host-device mesh: sharding rules,
collective matmul, gradient compression, dry-run cells at reduced scale.

These tests spawn a subprocess with XLA_FLAGS for 8 placeholder devices
(the main test process must keep seeing 1 device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
"""


def _run(body: str) -> str:
    code = _PRELUDE + textwrap.dedent(body)
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"}
    # Propagate the platform pin: without it jax probes for accelerators
    # in the stripped subprocess env (TPU metadata fetch retries cost
    # minutes per test on CPU-only hosts).
    if "JAX_PLATFORMS" in os.environ:
        env["JAX_PLATFORMS"] = os.environ["JAX_PLATFORMS"]
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_param_sharding_rules_shard_big_weights():
    out = _run("""
    from repro import configs
    from repro.models import transformer
    from repro.sharding.rules import param_sharding, spec_for_path
    cfg = configs.get("qwen3-8b")
    shapes = transformer.param_shapes(cfg)
    sh = param_sharding(shapes, mesh)
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    sharded = 0
    for path, s in flat:
        if any(a is not None for a in s.spec):
            sharded += 1
    print("SHARDED", sharded, len(flat))
    """)
    sharded, total = map(int, out.split()[1:3])
    assert sharded >= total * 0.5  # most tensors sharded


def test_optimizer_moments_share_param_sharding():
    out = _run("""
    from repro import configs
    from repro.sharding.rules import param_sharding
    from repro.train.train_step import train_state_shapes
    cfg = configs.get("qwen2-1.5b")
    params, opt = train_state_shapes(cfg)
    psh = param_sharding(params, mesh)
    osh = param_sharding(opt, mesh)
    # every m/v moment gets the same spec as its parameter
    ok = True
    pf = dict(jax.tree_util.tree_flatten_with_path(psh)[0])
    for path, s in jax.tree_util.tree_flatten_with_path(osh["m"])[0]:
        pspec = [v for k, v in pf.items() if tuple(k) == tuple(path)]
        if pspec and pspec[0].spec != s.spec:
            ok = False
    print("MOMENTS_OK", ok)
    """)
    assert "MOMENTS_OK True" in out


def test_collective_matmul_matches_einsum():
    out = _run("""
    from repro.sharding.collective_matmul import collective_matmul
    key = jax.random.key(0)
    B, S, D, F = 2, 8, 32, 64
    x = jax.random.normal(key, (B, S, D), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (D, F), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, "model")))
    ws = jax.device_put(w, NamedSharding(mesh, P(None, "model")))
    y = collective_matmul(xs, ws, mesh)
    expect = x @ w
    err = float(jnp.abs(y - expect).max() / jnp.abs(expect).max())
    print("ERR", err)
    """)
    assert float(out.split()[1]) < 1e-2  # bf16 accumulate inside


def test_grad_compression_cross_pod():
    out = _run("""
    import os
    from repro.train.grad_compression import compress_reduce_pod
    mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                          axis_types=(AxisType.Auto,) * 3)
    g = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8) / 64.0}
    # replicate across pods with different values -> psum averages them
    def make(v):
        return {"w": g["w"] + v}
    # place replicated
    gs = jax.device_put(g, NamedSharding(mesh3, P()))
    red, err = compress_reduce_pod(gs, None, mesh3, method="int8_ef")
    expect = g["w"]  # identical on both pods -> average == itself
    delta = float(jnp.abs(red["w"] - expect).max())
    maxerr = float(jnp.abs(err["w"]).max())
    print("DELTA", delta, "ERRSTATE", maxerr)
    """)
    parts = out.split()
    assert float(parts[1]) < 1e-2       # quantization error small
    assert float(parts[3]) < 1e-2       # error-feedback state bounded


@pytest.mark.parametrize("arch,shape", [
    ("qwen2-1.5b", "train_4k"),
    ("mixtral-8x7b", "decode_32k"),
    ("falcon-mamba-7b", "long_500k"),
])
def test_dryrun_cell_compiles_on_small_mesh(arch, shape):
    """The dry-run machinery end-to-end on an 8-device placeholder mesh,
    reduced shapes (full 512-dev meshes are exercised by the real dryrun)."""
    out = _run(f"""
    import dataclasses
    from repro import configs
    from repro.launch import cells as cells_lib
    from repro.models.config import ShapeConfig
    cfg = configs.get_reduced("{arch}")
    base = cells_lib.SHAPES["{shape}"]
    small = ShapeConfig(base.name, base.kind, seq_len=256, global_batch=4)
    plan = cells_lib.plan_cell(cfg, small, mesh)
    cell = cells_lib.build_cell(cfg, small, mesh, plan=plan)
    compiled = cells_lib.lower_cell(cell, mesh).compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jax: one dict per device
        ca = ca[0]
    print("OK", ma.temp_size_in_bytes, float(ca.get("flops", 0.0)))
    """)
    assert out.startswith("OK")
    assert float(out.split()[2]) > 0  # nonzero flops counted
