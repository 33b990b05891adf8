"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived,tier`` CSV rows (``tier`` is empty for
global rows; QoS benchmarks emit one row per priority tier and the CI
gate regresses them per tier):
  * bench_edram    — Table I / Fig. 2d / Fig. 5 / Fig. 10b (cell physics)
  * bench_hw       — Fig. 7 (3D vs 2D) + Fig. 8 (ISC vs SRAM) ratios
  * bench_ts       — Sec. III core-op throughput
  * bench_denoise  — Fig. 10 ROC/AUC + Fig. 12 polarity ablation
  * bench_classify — Table II frame/video accuracy protocol
  * bench_recon    — Table III SSIM protocol
  * bench_serve    — streaming engine: events/sec + readout latency vs
                     concurrent sensor count
  * bench_stream   — real-time runtime: coalesced+pipelined replay vs
                     per-chunk synchronous serving, latency percentiles,
                     overload/churn drop accounting

Run everything:    PYTHONPATH=src python -m benchmarks.run
Run a subset:      PYTHONPATH=src python -m benchmarks.run --only hw,edram
On a GPU box:      PYTHONPATH=src python -m benchmarks.run --platform gpu

``--platform`` routes through ``repro.platform`` (the one module that
owns pre-backend-init process configuration): selecting ``gpu`` also
installs the latency-oriented ``XLA_FLAGS`` serving profile, and the
resolved platform summary (``repro.platform.describe()``) is printed to
stderr with every run so an artifact can always be traced to the
backend and kernel path that produced it.

``--json DIR`` additionally writes one machine-readable
``BENCH_<module>.json`` artifact per module (rows + wall time + git sha
+ the resolved platform: backend, device count, kernel backend, and the
canonical ``key`` string that ``trend.py``/``compare.py`` use to keep
per-platform trend histories separate — a GPU run can never poison the
CPU rolling median) — the format ``benchmarks/compare.py`` and the CI
regression gate consume.  Arguments are strict: unknown flags and unknown ``--only``
names are errors, not silent no-ops (a typo'd flag must fail the build,
not skip the gate).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import traceback

MODULES = ["edram", "hw", "ts", "denoise", "classify", "recon", "serve",
           "stream"]

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def git_sha() -> str:
    """Current commit (CI env first, then git; 'unknown' offline)."""
    sha = os.environ.get("GITHUB_SHA")
    if sha:
        return sha
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=_REPO, timeout=10,
        )
        return out.stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 — best-effort metadata only
        return "unknown"


def norm_row(row):
    """Rows are (name, us, derived) or (name, us, derived, tier) — the
    4th element tags a per-tier QoS row (None = global)."""
    if len(row) == 3:
        return (*row, None)
    name, us, derived, tier = row
    return (name, us, derived, tier)


def platform_meta(desc: dict) -> dict:
    """Artifact platform block from ``repro.platform.describe()``: the
    fields that make two runs comparable, plus the canonical ``key``
    string the trend history segregates on."""
    backend = desc.get("backend", "unknown")
    n_dev = desc.get("n_devices", 0)
    kernel = desc.get("kernel_backend", "unknown")
    return {
        "backend": backend,
        "n_devices": n_dev,
        "kernel_backend": kernel,
        "key": f"{backend}:{n_dev}dev:{kernel}",
    }


def write_artifact(json_dir: str, name: str, rows, wall_s: float,
                   sha: str, failed: bool,
                   platform: dict | None = None) -> str:
    """One ``BENCH_<module>.json`` per module: the machine-readable twin
    of the CSV rows, with enough provenance to diff across commits."""
    os.makedirs(json_dir, exist_ok=True)
    path = os.path.join(json_dir, f"BENCH_{name}.json")
    payload = {
        "module": name,
        "git_sha": sha,
        "wall_s": round(wall_s, 3),
        "failed": failed,
        "platform": platform or {},
        "rows": [
            {"name": rn, "us_per_call": us, "derived": derived,
             "tier": tier}
            for rn, us, derived, tier in rows
        ],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser(
        description="3DS-ISC benchmark harness (CSV to stdout, optional "
                    "JSON artifacts)"
    )
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(MODULES))
    ap.add_argument("--json", default=None, metavar="DIR",
                    help="also write one BENCH_<module>.json per module "
                         "into DIR (the CI regression-gate artifact)")
    ap.add_argument("--platform", default=None,
                    choices=["cpu", "gpu", "tpu"],
                    help="jax platform to benchmark on (default: jax "
                         "auto-detect; 'gpu' also applies the serving "
                         "XLA_FLAGS profile via repro.platform)")
    # strict parsing: parse_known_args silently ignored typo'd flags
    # (`--onIy serve` ran the full suite and CI stayed green)
    args = ap.parse_args()
    which = args.only.split(",") if args.only else list(MODULES)
    unknown = sorted(set(which) - set(MODULES))
    if unknown:
        ap.error(
            f"unknown benchmark module(s): {', '.join(unknown)} "
            f"(choose from: {', '.join(MODULES)})"
        )

    from repro import platform as pf

    pf.set_platform(args.platform)
    pf.use_compile_cache()
    desc = pf.describe()
    print(f"# platform: {desc}", file=sys.stderr)
    platform = platform_meta(desc)

    sha = git_sha()
    print("name,us_per_call,derived,tier")
    failed = []
    for name in which:
        t0 = time.time()
        rows = []
        ok = True
        try:
            mod = __import__(f"benchmarks.bench_{name}", fromlist=["rows"])
            for row in mod.rows():
                row_name, us, derived, tier = norm_row(row)
                rows.append((row_name, us, derived, tier))
                us_s = f"{us:.1f}" if us is not None else ""
                dv = f"{derived:.4f}" if derived is not None else ""
                print(f"{row_name},{us_s},{dv},{tier or ''}", flush=True)
        except Exception:  # noqa: BLE001 — keep the harness running
            print(f"bench_{name},ERROR,,", flush=True)
            traceback.print_exc(file=sys.stderr)
            failed.append(name)
            ok = False
        wall = time.time() - t0
        print(f"# bench_{name} wall: {wall:.1f}s", file=sys.stderr)
        if args.json:
            path = write_artifact(args.json, name, rows, wall, sha,
                                  failed=not ok, platform=platform)
            print(f"# wrote {path}", file=sys.stderr)
    if failed:
        # every remaining module still ran, but CI must see the failure
        # (bench_serve/bench_stream rows assert bit-identity gates and
        # speedup floors, not just timings)
        print(f"# FAILED: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
