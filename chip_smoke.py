#!/usr/bin/env python3
"""Chip smoke: drive the serving program once on a TPU, at the published
widths of qwen2-1.5b (28 layers, d_model 1536, 12/2 heads, d_ff 8960,
vocab 151936; float32 weights from a fixed seed), and check what comes out.

    python3 chip_smoke.py                # one chip, phases (a)-(e)
    python3 chip_smoke.py --four-chips   # 1 router -> 4 replicas vs 1 replica

One chip:
  (a) the device as JAX reports it; anything but a TPU exits non-zero;
  (b) the flat and paged flash-decode kernels against their jnp oracle on
      the device, at qwen2-1.5b head widths, 8 slots, L = 2048 and 1088
      (off the 128-lane grid), and the paged kernel at the served page
      size — and the compiled calls contain the kernel (tpu_custom_call);
  (c) full-width logits of prefill + cached decode with the flash kernel
      against the dense path, and the engine's compiled decode windows
      (flat and paged) contain the kernel, not the oracle or dense math;
  (d) the fabric program (Registry -> Router -> EngineServer, from
      ``launch/serve.build_program``): warm-up compile seconds, then 3
      clients x 4 requests of 1000-token prompts, 32 new tokens each —
      every request must come back whole;
  (e) (d) again with the paged KV pool.
--four-chips runs only the fabric on four chips: the same prompts through
one router to four replicas (one per chip) and to one replica; greedy
outputs must match per prompt, every chip must hold a replica, and every
replica must have served.

The last line of stdout is one JSON object, printed only when every phase
passed: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent

ARCH = "qwen2-1.5b"
SLOTS = 8                 # EngineServer's KV slots
PROMPT_LEN = 1000
MAX_NEW = 32
CLIENTS, REQUESTS = 3, 4
PAGE_SIZE = 16            # the paged phase's page size (the kernel tiles it)
# bf16 outputs of both kernels round to 8 mantissa bits; the kernel and
# the oracle both accumulate in float32, so they differ by about one bf16
# ulp of outputs whose magnitude stays below the largest |v| (~4.5 here).
KERNEL_TOL = 2e-2
# Flash vs dense decode: the dense path rounds attention logits and
# weights to bf16 where the kernel keeps float32, and those differences
# pass through 28 residual layers. Bound: 5% of the largest |logit|.
LOGIT_REL_TOL = 5e-2
REPLICA_BYTES = 6e9       # float32 qwen2-1.5b weights alone are ~6.1e9


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


# -- (a) -----------------------------------------------------------------

def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def bytes_in_use() -> list[int]:
    import jax
    return [d.memory_stats()["bytes_in_use"] for d in jax.devices()]


# -- (b) -----------------------------------------------------------------

def _ragged_valid(rng, B, L):
    """Per-row live lengths: one full row, one empty row (its contract is
    zeros), the rest random."""
    import numpy as np
    lens = rng.integers(1, L + 1, B)
    lens[0], lens[1] = L, 0
    return np.arange(L)[None, :] < lens[:, None]


def _kernel_in(fn, *args) -> bool:
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def check_kernels(cfg, lengths=(2048, 1088), page_size=PAGE_SIZE,
                  B=SLOTS) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    H, KV, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rng = np.random.default_rng(0)
    key = jax.random.key(0)

    def rand(shape):
        nonlocal key
        key, sub = jax.random.split(key)
        return jax.random.normal(sub, shape, jnp.bfloat16)

    def flat(q, k, v, m):
        return ops.decode_attention(q, k, v, m)

    def paged(q, kp, vp, pg, m):
        return ops.paged_decode_attention(q, kp, vp, pg, m)

    cases = []
    for L in lengths:
        args = (rand((B, H, dh)), rand((B, L, KV, dh)), rand((B, L, KV, dh)),
                jnp.asarray(_ragged_valid(rng, B, L)))
        ref = ops.decode_attention(*args, impl="ref")
        cases.append((f"flat  B={B} L={L}", flat, args, ref))
    n = 2048 // page_size
    P = B * n + 1                       # every row's pages + the trash page
    pages = jnp.asarray(rng.permutation(np.arange(1, P)).reshape(B, n)
                        .astype(np.int32))
    args = (rand((B, H, dh)), rand((P, page_size, KV, dh)),
            rand((P, page_size, KV, dh)), pages,
            jnp.asarray(_ragged_valid(rng, B, n * page_size)))
    ref = ops.paged_decode_attention(*args, impl="ref")
    cases.append((f"paged B={B} ps={page_size} n={n}", paged, args, ref))

    for name, fn, args, ref in cases:
        out = jax.jit(fn)(*args)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        kernel = _kernel_in(fn, *args)
        say(f"  {name}: max|kernel - oracle| = {err:.3e} "
            f"(bf16 tol {KERNEL_TOL:.0e}), tpu_custom_call={kernel}")
        check(err <= KERNEL_TOL, f"{name}: kernel error {err} > {KERNEL_TOL}")
        check(bool(jnp.all(out[1] == 0)), f"{name}: empty row is not zeros")
        check(kernel, f"{name}: compiled call has no Pallas kernel")


# -- (c) -----------------------------------------------------------------

def init_params(cfg):
    import jax
    from repro.models import transformer
    params = transformer.init_params(cfg, jax.random.key(0))
    return jax.block_until_ready(params)


def check_flash_vs_dense(cfg, params, prompt_len=PROMPT_LEN, steps=4,
                         batch=2) -> None:
    """Prefill, then ``steps`` cached decode steps along the same greedy
    token path, with the flash kernel and with the dense attention."""
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer

    L = prompt_len + steps
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (batch, prompt_len), dtype=np.int32))
    prefill = jax.jit(functools.partial(transformer.prefill, cfg,
                                        context_len=L))
    step = {impl: jax.jit(functools.partial(transformer.decode_step, cfg,
                                            attn_impl=impl))
            for impl in ("flash", "dense")}
    logits, state0 = prefill(params, tokens=tokens)
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    state = {"flash": state0, "dense": state0}
    for i in range(steps):
        t = jnp.full((batch,), prompt_len + i, jnp.int32)
        out = {}
        for impl in ("flash", "dense"):
            out[impl], state[impl] = step[impl](params, state[impl], tok, t)
        f, d = (out[k].astype(jnp.float32) for k in ("flash", "dense"))
        err = float(jnp.max(jnp.abs(f - d)))
        scale = float(jnp.max(jnp.abs(d)))
        agree = float(jnp.mean(jnp.argmax(f, -1) == jnp.argmax(d, -1)))
        say(f"  decode step {i}: max|flash - dense| = {err:.3e}, "
            f"max|logit| = {scale:.3e}, rel = {err / scale:.3e} "
            f"(tol {LOGIT_REL_TOL:.0e}), argmax agree {agree:.2f}")
        check(np.isfinite(scale) and err <= LOGIT_REL_TOL * scale,
              f"flash vs dense logits differ by {err} at step {i}")
        tok = jnp.argmax(d, -1).astype(jnp.int32)


def check_decode_windows(cfg, context_len=PROMPT_LEN + MAX_NEW,
                         page_size=PAGE_SIZE) -> None:
    """The engine's compiled fused decode windows, as it builds them
    (``attn_impl="auto"``), must contain the Pallas kernel."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models import transformer
    from repro.serve import decode as serve_lib

    params = transformer.param_shapes(cfg)
    toks = jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32)
    t = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    n = -(-context_len // page_size)
    flat = transformer.decode_state_spec(cfg, SLOTS,
                                         ops.decode_cache_len(context_len))
    pool = transformer.decode_state_spec(cfg, SLOTS, n * page_size,
                                         page_size=page_size,
                                         num_pages=SLOTS * n + 1)
    pages = jax.ShapeDtypeStruct((SLOTS, n), jnp.int32)
    for name, k, args in [("flat K=8", 8, (params, flat, toks, t, None)),
                          ("paged K=1", 1, (params, pool, toks, t, None,
                                            pages)),
                          ("paged K=8", 8, (params, pool, toks, t, None,
                                            pages))]:
        fn = serve_lib.cached_fused_step(cfg, k, temperature=0.0)
        text = fn.lower(*args).compile().as_text()
        kernel = "tpu_custom_call" in text
        say(f"  engine decode window {name}: tpu_custom_call={kernel}")
        check(kernel, f"decode window {name} runs no Pallas kernel")


# -- (d), (e) --------------------------------------------------------------

def serve_phase(cfg, page_size=None) -> dict:
    """The fabric program as a user launches it; returns the meter summary."""
    from repro import core as lp
    from repro.core import telemetry
    from repro.launch import serve

    warm = telemetry.metrics().histogram("engine.warmup_s")
    warm.reset()
    with tempfile.TemporaryDirectory() as d:
        meter_json = os.path.join(d, "meter.json")
        program = serve.build_program(
            cfg, num_clients=CLIENTS, requests_per_client=REQUESTS,
            prompt_len=PROMPT_LEN, max_new=MAX_NEW, num_slots=SLOTS,
            replicas=1, routers=1, page_size=page_size,
            meter_json=meter_json)
        t0 = time.perf_counter()
        lp.launch_and_wait(program, timeout_s=900)
        wall = time.perf_counter() - t0
        with open(meter_json) as f:
            summary = json.load(f)
    total = CLIENTS * REQUESTS
    say(f"  set-up: decode-window warm-up compile {warm.vmax:.1f} s "
        f"(program wall {wall:.1f} s, first prefill compiles in-request)")
    # Clients start with the program, so each latency includes the
    # replica's start-up (weights, warm-up, first prefill compile).
    say(f"  served {summary['count']}/{total} requests, p50 "
        f"{summary['p50_ms']:.1f} ms from program start, reply lengths "
        f"{summary['out_len_min']}..{summary['out_len_max']} "
        f"(want {PROMPT_LEN + MAX_NEW})")
    check(warm.count == 1, "the replica never finished its warm-up")
    check(summary["count"] == total, f"served {summary['count']} != {total}")
    check(summary["out_len_min"] == summary["out_len_max"]
          == PROMPT_LEN + MAX_NEW, "a reply is not prompt + max_new tokens")
    return summary


# -- four chips ---------------------------------------------------------------

class Probe:
    """Program node: waits until the router sees every replica, sends the
    prompts concurrently, and records the replies, the router's per-replica
    dispatch counts and each device's bytes in use before stopping."""

    def __init__(self, router, prompts, replicas: int, result):
        self._router = router
        self._prompts = prompts
        self._replicas = replicas
        self._result = result

    def run(self):
        import numpy as np
        from repro import core as lp
        deadline = time.monotonic() + 600
        while len(self._router.stats()["replicas"]) < self._replicas:
            check(time.monotonic() < deadline, "replicas never registered")
            time.sleep(0.5)
        futs = [self._router.futures.submit(p) for p in self._prompts]
        self._result.outputs = [np.asarray(f.result(timeout=600))
                                for f in futs]
        stats = self._router.stats()["replicas"]
        self._result.dispatched = {name: r["dispatched"]
                                   for name, r in stats.items()}
        self._result.bytes_in_use = bytes_in_use()
        lp.stop_program()


def fabric_outputs(cfg, prompts, replicas: int):
    import types
    from repro import core as lp
    from repro.launch import serve
    program = serve.build_program(
        cfg, num_clients=0, requests_per_client=0, prompt_len=PROMPT_LEN,
        max_new=MAX_NEW, num_slots=SLOTS, replicas=replicas, routers=1)
    router = program.groups["router"].nodes[0].create_handle()
    # A plain object, not a dict: node arguments are rebuilt container by
    # container when handles are resolved, which would hand the node a copy.
    result = types.SimpleNamespace()
    with program.group("probe"):
        program.add_node(lp.PyNode(Probe, router, prompts, replicas, result))
    t0 = time.perf_counter()
    lp.launch_and_wait(program, timeout_s=900)
    say(f"  {replicas} replica(s): wall {time.perf_counter() - t0:.1f} s, "
        f"dispatched {sorted(result.dispatched.values())}, bytes in use "
        f"per device {[f'{b / 1e9:.2f}G' for b in result.bytes_in_use]}")
    return result


def four_chips(cfg) -> None:
    import numpy as np
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, PROMPT_LEN, dtype=np.int32)
               for _ in range(CLIENTS * REQUESTS)]
    say("[4] 1 router -> 1 replica (reference)")
    one = fabric_outputs(cfg, prompts, 1)
    gc.collect()
    say("[4] 1 router -> 4 replicas, one per chip")
    four = fabric_outputs(cfg, prompts, 4)
    same = [np.array_equal(a, b) for a, b in zip(one.outputs, four.outputs)]
    say(f"  greedy outputs identical per prompt: {sum(same)}/{len(same)}")
    check(all(len(o) == PROMPT_LEN + MAX_NEW for o in four.outputs),
          "a reply is not prompt + max_new tokens")
    check(all(same), "4-replica outputs differ from the 1-replica outputs")
    check(len(four.bytes_in_use) == 4
          and min(four.bytes_in_use) >= REPLICA_BYTES,
          "not every chip holds a replica")
    check(len(four.dispatched) == 4 and min(four.dispatched.values()) >= 1,
          "not every replica served a request")


# -- main -----------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 1-router -> 4-replica fabric path "
                         "and its 1-replica comparison")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke.py: no src/repro next to {ROOT}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro import configs
    from repro.launch.cache import enable_compile_cache
    say(f"compile cache: {enable_compile_cache()}")

    info = device_info()
    say(f"[a] device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        print("chip_smoke.py: no TPU found; this smoke runs only on the "
              "chip", file=sys.stderr)
        return 1
    cfg = configs.get(ARCH)
    if args.four_chips:
        check(info["count"] >= 4, f"--four-chips needs 4 chips, "
              f"found {info['count']}")
        four_chips(cfg)
    else:
        say(f"[b] decode kernels vs oracle at {ARCH} head widths")
        check_kernels(cfg)
        say(f"[c] {ARCH} full-width logits: flash kernel vs dense")
        params = init_params(cfg)
        check_flash_vs_dense(cfg, params)
        del params
        gc.collect()
        check_decode_windows(cfg)
        say(f"  device bytes in use after (c): {bytes_in_use()[0] / 1e9:.2f}G")
        say(f"[d] fabric, flat KV: {CLIENTS} clients x {REQUESTS} requests, "
            f"prompt {PROMPT_LEN}, max_new {MAX_NEW}")
        serve_phase(cfg)
        gc.collect()
        say(f"[e] fabric, paged KV (page_size {PAGE_SIZE})")
        serve_phase(cfg, page_size=PAGE_SIZE)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
