"""Benchmark harness: one module per paper table/figure plus pipeline perf.

Prints ``name,us_per_call,derived`` CSV rows; every row is also appended to
``BENCH_results.json`` so the perf trajectory is tracked across PRs, and the
run ends with an aggregate summary of that file.

``--gate`` skips the benchmarks and instead replays the stored history as a
regression gate: for every record lineage (same ``name`` + same ``config``),
the latest ``us_per_call`` is compared against the best earlier run; any
lineage more than ``--threshold`` (default 20%) slower fails the gate.
CI runs this as a non-blocking step so perf cliffs are visible per PR
without flaking the build on shared-runner noise.
"""
import argparse
import json


def lineage(rec: dict) -> tuple:
    """A record's comparison key: same name + same config = same lineage.
    Timestamps are deliberately excluded — runs of one lineage across PRs
    form the trajectory the gate walks."""
    return (rec.get("name", "unnamed"),
            json.dumps(rec.get("config", {}), sort_keys=True))


def check_gate(data: list, threshold: float = 0.2) -> list:
    """Regressed lineages in ``data`` (file order = run order).

    Returns ``[(name, config_json, best_us, latest_us)]`` for every lineage
    whose latest ``us_per_call`` exceeds the best earlier run by more than
    ``threshold``. Lineages with fewer than two timed runs never fail.
    """
    groups: dict = {}
    for rec in data:
        if not isinstance(rec, dict):
            continue
        us = rec.get("metrics", {}).get("us_per_call", rec.get("us_per_call"))
        if not isinstance(us, (int, float)) or us <= 0:
            continue
        groups.setdefault(lineage(rec), []).append(float(us))
    regressions = []
    for (name, cfg), runs in sorted(groups.items()):
        if len(runs) < 2:
            continue
        best, latest = min(runs[:-1]), runs[-1]
        if latest > best * (1.0 + threshold):
            regressions.append((name, cfg, best, latest))
    return regressions


def gate_main(path: str, threshold: float) -> int:
    try:
        with open(path) as f:
            data = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError) as e:
        print(f"perf gate: cannot read {path}: {e}")
        return 1
    regressions = check_gate(data, threshold=threshold)
    if not regressions:
        print(f"perf gate: OK ({path}, threshold {threshold:.0%})")
        return 0
    print(f"perf gate: {len(regressions)} regression(s) "
          f"(>{threshold:.0%} over the lineage's best run):")
    for name, cfg, best, latest in regressions:
        print(f"  {name} {cfg}: best {best:.1f}us -> latest {latest:.1f}us "
              f"({latest / best:.2f}x)")
    return 1


def run_benchmarks() -> None:
    print("name,us_per_call,derived")
    from . import fig1_quant_sparsity, table1_resources, fig4_energy
    from . import table2_direct_rate, table3_throughput, roofline
    from . import hybrid_pipeline
    table1_resources.run()
    fig4_energy.run()
    table2_direct_rate.run()
    table3_throughput.run()
    fig1_quant_sparsity.run()
    roofline.run()
    hybrid_pipeline.run()

    from .common import RESULTS_PATH, aggregate
    summary = aggregate()
    print(f"\n# BENCH_results.json aggregate ({RESULTS_PATH}):")
    for name, entry in sorted(summary.items()):
        latest = entry["latest_us"]
        latest_s = f"{latest:.1f}us" if isinstance(latest, (int, float)) else "-"
        print(f"#   {name}: runs={entry['runs']} latest={latest_s}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--gate", action="store_true",
                    help="perf-regression gate over BENCH_results.json "
                         "instead of running benchmarks (exit 1 on any "
                         "lineage regressing past --threshold)")
    ap.add_argument("--threshold", type=float, default=0.2,
                    help="fractional slowdown tolerated vs the lineage's "
                         "best run (default 0.2 = 20%%)")
    ap.add_argument("--results", default="",
                    help="results file (default: benchmarks.common."
                         "RESULTS_PATH, honouring $BENCH_RESULTS)")
    args = ap.parse_args()
    if args.gate:
        from .common import RESULTS_PATH
        raise SystemExit(gate_main(args.results or RESULTS_PATH,
                                   args.threshold))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run_benchmarks()


if __name__ == '__main__':
    main()
