"""Benchmark harness — one suite per paper table.

    PYTHONPATH=src python benchmarks/run.py                    # full CSV
    PYTHONPATH=src python benchmarks/run.py --smoke --json bench.json

Prints ``name,us_per_call,derived`` CSV rows (unchanged contract), and with
``--json`` also writes a structured artifact: per-suite rows + wall time, the
platform fingerprint and the active calibration fingerprint — the record CI
uploads on every PR so the perf trajectory is trackable across commits.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

# Invoked as `python benchmarks/run.py`, sys.path[0] is benchmarks/ itself;
# the suite imports need the repo root.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCHEMA_VERSION = 1


def _parse_row(raw: str) -> dict:
    name, us, derived = raw.split(",", 2)
    try:
        us_val = float(us)
    except ValueError:
        us_val = float("nan")
    return {"name": name, "us_per_call": us_val, "derived": derived}


def _suites(smoke: bool) -> list:
    from benchmarks import (
        bench_collaborative,
        bench_feature_extractor,
        bench_inventory,
        bench_kernels,
        bench_pipeline,
        bench_service,
        bench_usecase1_mlp,
        bench_usecase3_transformer,
    )

    if smoke:
        # The fast paper-table subset: small shapes, no Pallas-interpret or
        # full-inventory sweeps, sized for a per-PR CI job.
        return [
            ("usecase1_mlp(T5)", bench_usecase1_mlp.run),
            ("collaborative(T6)", lambda: bench_collaborative.run(flows=200)),
            ("usecase3_transformer", lambda: bench_usecase3_transformer.run(flows=100)),
            ("pipeline(streaming)", lambda: bench_pipeline.run(smoke=True)),
            ("service(frontend)", lambda: bench_service.run(smoke=True)),
        ]
    return [
        ("inventory(T4)", bench_inventory.run),
        ("usecase1_mlp(T5)", bench_usecase1_mlp.run),
        ("collaborative(T6)", bench_collaborative.run),
        ("usecase3_transformer", bench_usecase3_transformer.run),
        ("feature_extractor", bench_feature_extractor.run),
        ("kernels", bench_kernels.run),
        ("pipeline(streaming)", bench_pipeline.run),
        ("service(frontend)", bench_service.run),
    ]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description="run the paper-table benchmark suites")
    ap.add_argument("--smoke", action="store_true",
                    help="fast subset for per-PR CI (seconds, not minutes)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write a structured result artifact to PATH")
    ap.add_argument("--calibrated", action="store_true",
                    help="run under RuntimeConfig.calibrated() (falls back to "
                         "analytic defaults when no artifact exists)")
    args = ap.parse_args(argv)

    from repro.runtime import RuntimeConfig, current_runtime, octopus_runtime, platform

    platform.enable_compile_cache()
    ctx = (octopus_runtime(RuntimeConfig.calibrated()) if args.calibrated
           else contextlib.nullcontext())
    suites = _suites(args.smoke)
    results, failures = [], []
    print("name,us_per_call,derived")
    with ctx:
        active = current_runtime()
        for label, fn in suites:
            t0 = time.perf_counter()
            rows, error = [], None
            try:
                for r in fn():
                    print(r)
                    rows.append(_parse_row(r))
            except Exception as e:  # keep the harness going; record the failure
                error = repr(e)
                failures.append((label, error))
                print(f"{label},nan,ERROR={e!r}")
            if error is None and not rows:
                # A suite that silently emits nothing would hollow out the
                # trajectory gate — treat it like a raise.
                error = "no rows emitted"
                failures.append((label, error))
                print(f"{label},nan,ERROR='no rows emitted'")
            wall = time.perf_counter() - t0
            results.append({"suite": label, "wall_s": wall, "rows": rows,
                            "error": error})
            sys.stderr.write(f"[bench] {label} done in {wall:.1f}s\n")

    if args.json:
        artifact = {
            "schema_version": SCHEMA_VERSION,
            "smoke": args.smoke,
            "platform": platform.fingerprint(),
            "calibration": active.calibration,
            "runtime": {"policy": active.policy, "tau": active.tau,
                        "vpe_max_elems": active.vpe_max_elems,
                        "use_pallas": active.use_pallas,
                        "interpret": active.interpret,
                        "quantize": active.quantize,
                        "quant_impl": active.quant_impl,
                        "quant_scales": (active.quant_scales.fingerprint
                                         if active.quant_scales is not None
                                         else None)},
            "created_unix": time.time(),
            "suites": results,
        }
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=1)
        sys.stderr.write(f"[bench] wrote {args.json}\n")

    if failures:
        sys.stderr.write(f"[bench] FAILURES: {failures}\n")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
