#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest arrival rate it sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 4,8,12

One process sets the cell up once, as ``bench/run.py`` does, then runs one
open-loop window per rate (lowest first) through the same path, and
prints a JSON line per rate: requests, how many were answered inside the
window, p50/p90 latency from the due time, the p50 of the window's last
third over its first third (a growing backlog shows as a rising ratio),
and how many were still outstanding at the close. The knee is the highest
rate whose latency stays flat across the window; a cell's mix is set at
about three quarters of it. Not part of a benchmark run.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse      # noqa: E402
import json          # noqa: E402
import pathlib       # noqa: E402
import sys           # noqa: E402
import types         # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from bench.run import (CompileCounter, Rec, build_program,  # noqa: E402
                       find_cell, load_benchmark, load_config, open_loop,
                       serve_seeded_weights, use_compile_cache,
                       wait_for_replicas, warm)


def summarize(rate: float, recs: list, seconds: float) -> dict:
    import numpy as np
    ok = [r for r in recs if r.ok]
    lat = np.array([(r.done - r.due_s) * 1e3 for r in ok])
    thirds = [[(r.done - r.due_s) for r in ok
               if k * seconds / 3 <= r.due_s < (k + 1) * seconds / 3]
              for k in range(3)]
    trend = (float(np.median(thirds[2]) / np.median(thirds[0]))
             if thirds[0] and thirds[2] else float("nan"))
    return {"rate_per_s": rate, "requests": len(recs), "ok": len(ok),
            "answered_in_window": sum(1 for r in ok if r.done <= seconds),
            "outstanding_at_close": sum(1 for r in recs if r.sent is not None
                                        and (r.done is None
                                             or r.done > seconds)),
            "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
            "p90_ms": float(np.percentile(lat, 90)) if len(lat) else None,
            "late_third_over_first": trend,
            "max_lag_ms": max(((r.sent - r.due_s) * 1e3 for r in recs
                               if r.sent is not None), default=None)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = sorted(float(r) for r in args.rates.split(","))

    bench = load_benchmark()
    cell = find_cell(bench, args.workload)
    cfg = load_config(bench, cell["config"])
    from bench import generator
    mix = generator.load_mix(cell["traffic"])
    import jax
    use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("bench/sweep.py: no TPU found", file=sys.stderr)
        return 1
    counter = CompileCounter()
    vocab = cfg["model"]["vocab_size"]

    def job(router, reps, st):
        wait_for_replicas(router, reps)
        warm(reps, generator.warm_requests(mix, vocab, args.seed))
        print(f"set-up {time.monotonic() - T0:.1f} s", flush=True)
        for i, rate in enumerate(rates):
            m = dict(mix, rate_per_s=rate)
            recs = [Rec(r.prompt, r.max_new, r.shared, r.due_s) for r in
                    generator.open_schedule(m, args.seconds, vocab,
                                            args.seed + i)]
            counter.count, counter.armed = 0, True
            open_loop(router, recs, args.seconds, 60.0)
            counter.armed = False
            row = summarize(rate, recs, args.seconds)
            row["compiles_in_window"] = counter.count
            print(json.dumps(row), flush=True)

    serve_seeded_weights(cfg, args.seed)
    state = types.SimpleNamespace(error=None)
    from repro import core as lp
    lp.launch_and_wait(build_program(cfg, cell["chips"], job, state),
                       timeout_s=3600.0).stop()
    if state.error is not None:
        raise state.error
    return 0


if __name__ == "__main__":
    sys.exit(main())
