"""Benchmark harness — one entry per paper table/figure plus the roofline
aggregation.  Prints ``name,us_per_call,derived`` CSV and exits non-zero
when any entry failed (its row then carries ``ERROR:<reason>``)."""

from __future__ import annotations

import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import (  # noqa: E402
    ablation,
    fig1,
    fig2,
    fig3,
    fig_async,
    fig_byzantine,
    fig_hetero,
    fig_lm,
    kernels_bench,
    roofline_table,
    sweep_bench,
)


def main() -> int:
    from repro.core.cache import setup_compilation_cache

    setup_compilation_cache()
    os.makedirs("results", exist_ok=True)
    rows = []
    benches = [
        ("fig1", lambda: [fig1.run("results/fig1.csv")]),
        ("fig2", lambda: [fig2.run("results/fig2.csv")]),
        ("fig3", lambda: [fig3.run("results/fig3.csv")]),
        ("fig_hetero", lambda: [fig_hetero.run("results/fig_hetero.csv")]),
        # bench_iters=None: the sweep entry below already measures the
        # gated engine-vs-host number at this config
        ("fig_async", lambda: [fig_async.run("results/fig_async.csv",
                                             bench_iters=None)]),
        ("ablation", lambda: [ablation.run("results/ablation.csv")]),
        ("sweep", lambda: [sweep_bench.run("results/BENCH_sweep.json")]),
        # after sweep_bench so the 'lm'/'byzantine' sections merge into its
        # fresh record
        ("fig_lm", lambda: [fig_lm.run("results/fig_lm.csv",
                                       bench_json="results/BENCH_sweep.json")]),
        ("fig_byzantine",
         lambda: [fig_byzantine.run("results/fig_byzantine.csv",
                                    bench_json="results/BENCH_sweep.json")]),
        ("kernels", kernels_bench.run),
        ("roofline", lambda: [roofline_table.run()]),
    ]
    failed = []
    for name, fn in benches:
        try:
            rows.extend(fn())
        except Exception as e:  # run the rest, report the failure, exit 1
            traceback.print_exc()
            failed.append(name)
            rows.append({"name": name, "us_per_call": -1.0, "derived": f"ERROR:{e}"})

    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    if failed:
        print(f"FAILED: {','.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
