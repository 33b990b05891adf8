"""Benchmark of the streaming time-surface service, one cell per run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout on a host with the chips the cell asks
for (``BENCHMARK.json``), in this one process.  It refuses to run, with
a non-zero exit and no result, when JAX finds no TPU, fewer chips than
the cell asks for, or a device kind without peak rates in
``bench/peaks.py``.  Earlier lines (standard error) report set-up, the
window, compiles inside it and the comparison; the last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(HERE, "traffic")]

import harness  # noqa: E402
import peaks  # noqa: E402


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    cell = harness.load_cell(ROOT, args.workload)
    harness.import_program(ROOT)
    cache = harness.configure_jax(ROOT, cell.config)
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        fail(f"no TPU found (jax platform {backend!r}); this benchmark "
             "measures the chip and never falls back")
    chips = cell.workload["chips"]
    devices = jax.devices()
    if len(devices) < chips:
        fail(f"cell {cell.name} needs {chips} chips, found {len(devices)}")
    kind = devices[0].device_kind
    try:
        peaks.peak(kind)
    except KeyError as e:
        fail(str(e))
    harness.log(f"device: {devices[0].platform} {kind} x{len(devices)}; jax "
                f"{jax.__version__}; compile cache {cache}")

    res = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START, n_devices=chips)
    if args.trace:
        metrics = res["per_layer"]
    else:
        metrics = {m["name"]: {"value": res["e2e"][m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    device = {"platform": devices[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = res["busy_s"]
        device["window_s"] = res["trace_window_s"]
        out["breakdown"] = res["breakdown"]
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in res["checks"]}
    for name, m in metrics.items():
        harness.log(f"metric {name}: {m['value']} {m['unit']}")
    harness.log(f"correct: {res['correct']}")
    for name, v, lim in res["checks"]:
        harness.log(f"check {name}: {v} (limit {lim})")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
