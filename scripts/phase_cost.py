#!/usr/bin/env python3
"""What one ``telemetry.phase`` costs, with no profiler session running and
with one live, on this machine's default JAX device.

    PYTHONPATH=src python3 scripts/phase_cost.py [--n 100000]

Times ``n`` empty phases in a loop, and the same loop with no phase in it;
prints one JSON line: the device, and microseconds per iteration of each
loop. The profiler's trace is written under ``bench_out/`` and removed.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))


def per_iter_us(n: int, body) -> float:
    t0 = time.perf_counter()
    body(n)
    return (time.perf_counter() - t0) * 1e6 / n


def phases(n: int) -> None:
    from repro.core import telemetry
    for _ in range(n):
        with telemetry.phase("engine.window.k8"):
            pass


def empty(n: int) -> None:
    for _ in range(n):
        pass


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=100_000)
    args = ap.parse_args()
    import jax
    dev = jax.devices()[0]
    phases(10)                          # the lazy import, once
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "n": args.n, "empty_loop_us": per_iter_us(args.n, empty),
           "phase_no_session_us": per_iter_us(args.n, phases)}
    trace_dir = ROOT / "bench_out" / "phase_cost"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        out["phase_in_session_us"] = per_iter_us(args.n, phases)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
