"""Benchmark harness: one module per paper table/figure.

  table3   — II + compile time, decoupled vs joint mapper (paper Tab. III)
  fig5     — compile time vs CGRA size for `aes` (paper Fig. 5)
  hetero   — the suite on a heterogeneous arch preset (--arch), with
             execute_mapping capability verification (DESIGN.md §10)
  scale    — one kernel at 4x4..100x100 per space backend (exact vs
             anneal), execution-verified, with utilization (DESIGN.md §13)
  service  — compile-daemon load test: zipf/bursty/mixed-tenant trace over
             the unix socket, warm p50/p99 latency, admission-control sheds,
             speculative-premapping lift (DESIGN.md §16)

Each section also emits a ``BENCH_<name>.json`` artifact (consumed by CI and
by the Fig. 5 near-flat acceptance gate) and prints a
``name,us_per_call,derived`` CSV at the end. ``BENCH_table3.json`` carries
per-kernel rows in the unified ``repro.api.CompileResult`` schema plus
aggregate cache hit/miss counters, so service-layer gains — batch
parallelism, warm persistent cache — show up in the tracked artifacts.

Compiler flags (``--jobs``, ``--cache-dir``, ``--profile``, ``--arch``, ...)
are the shared :func:`repro.api.add_cli_args` set — resolved through the
same ``resolve_options`` path as every other CLI (DESIGN.md §11.1).

Full sweep:   ``PYTHONPATH=src python -m benchmarks.run``
CI smoke:     ``PYTHONPATH=src python -m benchmarks.run --smoke``
Service mode: ``PYTHONPATH=src python -m benchmarks.run --jobs 4 --cache-dir /tmp/maps``
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> None:
    from repro.api import add_cli_args, options_from_args

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small subset, short timeouts")
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI job: quick subset, no joint baseline, JSON artifacts only",
    )
    ap.add_argument("--skip-joint", action="store_true")
    ap.add_argument("--only",
                    choices=["table3", "fig5", "hetero", "scale", "service"])
    add_cli_args(ap)          # --jobs/--cache-dir/--profile/--arch/... (api)
    args = ap.parse_args(argv)
    if args.smoke:
        args.quick = True
        args.skip_joint = True
    options = options_from_args(args)
    from repro import obs

    # structured tracing (DESIGN.md §15): --trace records every section's
    # compile spans into one Perfetto-loadable trace file
    with obs.session(getattr(args, "trace_out", None), enable=options.trace):
        _run_sections(args, options)


def _run_sections(args, options) -> None:
    # the hetero section needs a heterogeneous target even when the shared
    # --arch flag is unset; table3/fig5 build their own homogeneous grids
    hetero_arch = options.arch or "satmapit_edge_mem_4x4"

    from benchmarks import (
        bench_fig5,
        bench_hetero,
        bench_scale,
        bench_table3,
    )

    csv_rows: list[tuple[str, float, str]] = []

    if args.only in (None, "table3"):
        # exact-check on: every row carries ii_opt + a machine-checkable
        # optimality certificate (DESIGN.md §14), which tools/
        # check_certificates.py re-verifies and CI gates regressions on.
        # The quick subset pivots on the 4x4 paper grid (the acceptance
        # fabric) with the full 17-kernel suite.
        kw = dict(options=options.replace(exact_check=True),
                  run_joint=not args.skip_joint)
        if args.quick:
            kw.update(sizes=(2, 4), ours_budget_s=20, joint_budget_s=20)
        else:
            kw.update(ours_budget_s=60, joint_budget_s=60)
        rows = bench_table3.run(**kw)
        for line in bench_table3.summarize(rows):
            print("TABLE3:", line)
        with open("BENCH_table3.json", "w") as f:
            json.dump(
                {
                    "jobs": options.jobs,
                    "cache": bench_table3.cache_counters(rows),
                    "rows": rows,
                },
                f, indent=2,
            )
        for r in rows:
            csv_rows.append(
                (
                    f"table3_{r['name']}_{r['size']}x{r['size']}",
                    r["wall_s"] * 1e6,
                    f"II={r.get('ii')};mII={r['mII']};CTR={r.get('ctr', '')}",
                )
            )

    if args.only in (None, "fig5"):
        # always span 4x4..20x20: the near-flat gate compares those endpoints
        sizes = (4, 10, 20) if args.quick else (2, 4, 6, 8, 10, 14, 20)
        rows = bench_fig5.run(options=options, sizes=sizes,
                              run_joint=not args.skip_joint,
                              joint_budget_s=20 if args.quick else 60)
        for r in rows:
            csv_rows.append(
                (
                    f"fig5_aes_{r['size']}x{r['size']}",
                    r["ours_time_s"] * 1e6,
                    f"joint_s={r.get('joint_time_s', '')}",
                )
            )

    if args.only in (None, "hetero"):
        kw = dict(arch=hetero_arch, options=options.replace(exact_check=True))
        if args.quick:
            kw.update(budget_s=20,
                      benchmarks=["bitcount", "fft", "gsm", "susan", "aes"])
        hrep = bench_hetero.run(**kw)
        with open("BENCH_hetero.json", "w") as f:
            json.dump(hrep, f, indent=2)
        for r in hrep["rows"]:
            csv_rows.append(
                (
                    f"hetero_{r['name']}_{r['arch']}",
                    r["wall_s"] * 1e6,
                    f"II={r['ii']};mII={r['mII']};verified={r['verified']}",
                )
            )

    if args.only in (None, "scale"):
        srep = bench_scale.run(options=options,
                               budget_s=15 if args.quick else 30)
        with open("BENCH_scale.json", "w") as f:
            json.dump(srep, f, indent=2)
        for r in srep["rows"]:
            occ = (r["utilization"] or {}).get("occupancy", "")
            csv_rows.append(
                (
                    f"scale_{r['name']}_{r['size']}x{r['size']}_{r['space_backend']}",
                    r["wall_s"] * 1e6,
                    f"II={r['ii']};verified={r['verified']};occupancy={occ}",
                )
            )

    if args.only in (None, "service"):
        from benchmarks import bench_service

        vrep = bench_service.run(options=options, smoke=args.quick)
        with open("BENCH_service.json", "w") as f:
            json.dump(vrep, f, indent=2)
        for line in bench_service.summarize(vrep):
            print("SERVICE:", line)
        csv_rows.append(
            ("service_warm_p99", vrep["warm_p99_ms"] * 1e3,
             f"p50_ms={vrep['warm_p50_ms']};shed_rate={vrep['shed_rate']};"
             f"spec_hits={vrep['speculate']['cold']['speculative_hits']}"))

    print("\nname,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us},{derived}")


if __name__ == "__main__":
    main()
