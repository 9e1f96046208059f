"""Benchmark driver: one module per paper table/figure + system substrate.

    PYTHONPATH=src python -m benchmarks.run [--only param_server,...] \
        [--json OUT.json]

Prints ``name,us_per_call,derived`` CSV (one row per measurement), and
with ``--json`` also writes the rows to a JSON file (e.g. BENCH_rpc.json
for the rpc_overhead suite — CI records these). Writing merges by suite:
rows from suites *not* rerun are kept, so the BENCH_rpc and BENCH_serve
workflows can share or alternate files without clobbering each other.
  * param_server  — paper Figure 2 (QPS: single vs replicated vs cached)
  * rpc_overhead  — paper §1 zero-overhead claim (direct vs inproc vs gRPC)
  * replay        — reverb-lite insert/sample throughput + rate limiter
  * kernels       — Pallas kernels (interpret) vs oracles + analytic bytes
  * roofline      — per-cell roofline terms from the dry-run artifacts
  * serve         — continuous-batching vs lockstep serving A/B
  * train         — elastic training fabric chaos arms (kill/shrink/grow)
"""

from __future__ import annotations

import argparse
import json
import os
import sys

SUITES = ("rpc_overhead", "replay", "kernels", "param_server", "roofline",
          "serve", "train")

# Row-name prefix -> suite, for JSON files written before rows carried an
# explicit "suite" field.
_PREFIX_SUITE = {"rpc/": "rpc_overhead", "replay/": "replay",
                 "kernel/": "kernels", "ps/": "param_server",
                 "roofline/": "roofline", "serve/": "serve",
                 "train/": "train"}

_rows: list[dict] = []
_suite: list[str] = ["?"]


def _emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.2f},{derived}", flush=True)
    _rows.append({"name": name, "us_per_call": round(us_per_call, 2),
                  "derived": derived, "suite": _suite[0]})


def _row_suite(row: dict) -> str:
    suite = row.get("suite")
    if suite:
        return suite
    for prefix, inferred in _PREFIX_SUITE.items():
        if row.get("name", "").startswith(prefix):
            return inferred
    return "?"


def _write_json(path: str, ran: set[str]) -> None:
    """Merge this run's rows into ``path`` by suite: rerun suites replace
    their old rows wholesale; everything else is preserved."""
    kept, suites = [], set()
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
            kept = [r for r in old.get("rows", [])
                    if _row_suite(r) not in ran]
            suites = {_row_suite(r) for r in kept} - {"?"}
        except (json.JSONDecodeError, OSError) as exc:
            print(f"ignoring unreadable {path}: {exc}", file=sys.stderr)
    rows = kept + _rows
    with open(path, "w") as f:
        json.dump({"suites": sorted(suites | ran), "rows": rows}, f,
                  indent=2)
        f.write("\n")
    print(f"wrote {len(_rows)} rows to {path} "
          f"({len(kept)} kept from other suites)", file=sys.stderr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(SUITES))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="merge the emitted rows into a JSON file")
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else set(SUITES)
    _rows.clear()

    def begin(suite: str):
        _suite[0] = suite
        return suite in only

    print("name,us_per_call,derived")
    if begin("rpc_overhead"):
        from benchmarks import rpc_overhead
        rpc_overhead.run(_emit)
    if begin("replay"):
        from benchmarks import replay_bench
        replay_bench.run(_emit)
    if begin("kernels"):
        from benchmarks import kernel_bench
        kernel_bench.run(_emit)
    if begin("param_server"):
        from benchmarks import param_server
        param_server.run(_emit)
    if begin("roofline"):
        from benchmarks import roofline_bench
        roofline_bench.run(_emit)
    if begin("serve"):
        from benchmarks import serve_bench
        serve_bench.run(_emit)
    if begin("train"):
        from benchmarks import train_bench
        train_bench.run(_emit)

    if args.json:
        _write_json(args.json, only & set(SUITES))


if __name__ == "__main__":
    main()
