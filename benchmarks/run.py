"""Benchmark orchestrator — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV. ``--full`` runs the paper-scale
concurrency sweeps (slow on CPU); default is the quick profile.

The ``prefill`` bench additionally persists its rows to ``BENCH_prefill.json``
(TTFT/TPOT at 8/32/64 concurrency), the ``prefix`` bench to
``BENCH_prefix.json`` (warm-vs-cold TTFT under a shared system prompt), and
the ``spec`` bench to ``BENCH_spec.json`` (speculative-vs-plain decode
throughput) so subsequent PRs have a perf trajectory to regress against.
The ``traffic`` bench persists its own ``BENCH_traffic.{json,html,md}``
(windowed SLO timeline + dashboard — see bench_traffic.py).
Persisted payloads are stamped with the git revision and a UTC timestamp.
A failing bench is reported and the others still run; the exit code is then
non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

PERSIST_JSON = {"prefill": "BENCH_prefill.json", "prefix": "BENCH_prefix.json",
                "spec": "BENCH_spec.json"}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names "
                         "(fig2,fig5,fig6,fig7,table1,fig8,kernels,prefill,"
                         "prefix,spec,traffic,chaos)")
    args = ap.parse_args()
    quick = not args.full

    from benchmarks import (bench_chaos, bench_fig2_breakdown,
                            bench_fig5_endpoints, bench_fig6_breakdown,
                            bench_fig7_throughput, bench_fig8_parallelism,
                            bench_kernels, bench_prefill, bench_prefix,
                            bench_spec, bench_table1_streaming, bench_traffic)
    from benchmarks.common import stamp, warmup
    from repro.launch.compile_cache import enable_compile_cache

    benches = {
        "fig2": bench_fig2_breakdown,
        "fig5": bench_fig5_endpoints,
        "fig6": bench_fig6_breakdown,
        "fig7": bench_fig7_throughput,
        "table1": bench_table1_streaming,
        "fig8": bench_fig8_parallelism,
        "kernels": bench_kernels,
        "prefill": bench_prefill,
        "prefix": bench_prefix,
        "spec": bench_spec,
        "traffic": bench_traffic,   # writes BENCH_traffic.{json,html,md} itself
        "chaos": bench_chaos,       # writes BENCH_chaos.{json,html,md} itself
    }
    selected = args.only.split(",") if args.only else list(benches)
    unknown = [n for n in selected if n not in benches]
    if unknown:
        ap.error(f"unknown bench name(s): {', '.join(unknown)} "
                 f"(registered: {', '.join(benches)})")

    enable_compile_cache()
    print("name,us_per_call,derived")
    warmup()
    failed = []
    for name in selected:
        mod = benches[name]
        t0 = time.time()
        try:
            rows = mod.run(quick=quick)
        except Exception as e:  # a failing bench must not hide the others
            print(f"{name}.ERROR,0,\"{type(e).__name__}: {e}\"", flush=True)
            failed.append(name)
            continue
        for r in rows:
            derived = json.dumps(r["derived"], default=str).replace('"', "'")
            print(f"{r['name']},{r['us_per_call']:.1f},\"{derived}\"", flush=True)
        if name in PERSIST_JSON:
            with open(PERSIST_JSON[name], "w") as f:
                json.dump({"bench": name, "quick": quick, **stamp(),
                           "rows": rows}, f, indent=2, default=str)
            print(f"# wrote {PERSIST_JSON[name]}", file=sys.stderr)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        print(f"# failed: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
