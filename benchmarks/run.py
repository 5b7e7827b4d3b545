"""Benchmark harness: one module per paper table/figure.

Section modules yield structured ``benchmarks.common.Row`` records; stdout
stays the familiar ``name,us_per_call,derived`` CSV (a *rendering* of the
rows), and ``--json out.json`` records the typed rows plus an environment
metadata block (backend, device count/kind, jax version, git sha,
timestamp) so the repo's ``BENCH_*.json`` perf trajectory stays
interpretable across machines and PRs. ``--only`` restricts to matching
sections (the CI smoke step); ``scripts/perf_check.py`` diffs two JSON
outputs and gates on regressions.
"""
import argparse
import json
import sys

from benchmarks.common import HEADER, Row, env_metadata


def collect(sections, out=sys.stdout):
    """Run every section, render rows to ``out``, return JSON records.
    A section that yields anything but ``Row`` objects is a hard error —
    the old CSV re-parsing silently mis-parsed free-form lines."""
    records = []
    print(HEADER, file=out)
    for mod, label in sections:
        print(f"# --- {label} ---", file=out)
        for r in mod.run():
            if not isinstance(r, Row):
                raise TypeError(
                    f"benchmark section {mod.__name__} yielded "
                    f"{type(r).__name__} ({r!r}); sections must yield "
                    f"benchmarks.common.Row")
            print(r.render(), file=out, flush=True)
            records.append(r.to_record(label))
    return records


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write rows + env metadata as structured JSON")
    ap.add_argument("--only", default=None,
                    help="run only sections whose module name contains one "
                         "of these comma-separated substrings")
    ap.add_argument("--timestamp", default=None,
                    help="timestamp recorded verbatim in the JSON metadata "
                         "(CI passes its own; defaults to now, UTC)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (argsort_bench, external_sort_bench, fig14_w_sweep,
                            fig15_full_sort, kernel_merge, merge_tree_bench,
                            moe_dispatch, moe_route_bench, serve_bench,
                            sharded_sort_bench, skew_balance,
                            table2_comparators)
    sections = [(table2_comparators, "Table 2 (comparator counts)"),
                (fig14_w_sweep, "Fig 14 (throughput vs w)"),
                (fig15_full_sort, "Fig 15 (complete sort)"),
                (skew_balance, "S4.1 (skewness optimisation)"),
                (merge_tree_bench, "S2.1 (parallel merge tree)"),
                (kernel_merge, "Pallas kernels (interpret)"),
                (argsort_bench, "Argsort variants (payload lanes)"),
                (moe_dispatch, "MoE dispatch via repro.engine"),
                (moe_route_bench, "DESIGN §9 (fused MoE routing op)"),
                (sharded_sort_bench, "S8.2 (sharded sample sort, all devices)"),
                (external_sort_bench, "DESIGN §8 (out-of-core external sort)"),
                (serve_bench, "DESIGN §10 (continuous-batching serve)")]
    if args.only:
        keys = [s.strip() for s in args.only.split(",") if s.strip()]
        sections = [(m, l) for m, l in sections
                    if any(k in m.__name__ for k in keys)]

    records = collect(sections)
    if args.json:
        timestamp = args.timestamp
        if timestamp is None:
            from datetime import datetime, timezone
            timestamp = datetime.now(timezone.utc).isoformat(
                timespec="seconds")
        doc = {"meta": env_metadata(timestamp), "rows": records}
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"# wrote {len(records)} rows to {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
