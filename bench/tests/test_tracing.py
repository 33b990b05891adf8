"""The trace reduction and the work counts behind the per-layer metrics,
on three deadlines cut from a chip trace (``data/``)."""
import json
import os

import numpy as np
import pytest

import harness
import peaks
import tracing
from conftest import BENCH, HERE

DATA = os.path.join(HERE, "data", "trace_qvga_3_deadlines.json")
QVGA = json.load(open(os.path.join(BENCH, "configs", "isc-qvga-tiered.json")))
PEAK = peaks.PEAKS["TPU v5 lite"]


@pytest.fixture(scope="module")
def data():
    return json.load(open(DATA))


@pytest.fixture(scope="module")
def trace(data):
    return tracing.Trace(data)


def raster(data, pred=lambda op: True):
    """Busy microseconds of the window, by painting every operation."""
    lo, dur = next((s[1], s[2]) for s in data["spans"] if s[0] == "bench.window")
    busy = np.zeros(int(dur // 1000) + 1, bool)
    for op in data["devices"]["/device:TPU:0"]["ops"]:
        if pred(op):
            a = max(0, int((op[1] - lo) // 1000))
            b = min(len(busy), int((op[1] + op[2] - lo) // 1000))
            busy[a:b] = True
    return busy


def test_busy_and_idle_share(data, trace):
    busy = raster(data)
    assert trace.window_s == pytest.approx(len(busy) * 1e-6, abs=2e-6)
    assert trace.busy_s() == pytest.approx(busy.sum() * 1e-6, rel=0.01)
    idle = harness.load_reader(os.path.dirname(BENCH), "device_idle_share")
    ctx = harness.Context(trace, [], QVGA, PEAK, trace.window_s)
    assert idle(ctx) == pytest.approx(100 * (1 - busy.mean()), abs=0.2)


def test_program_and_kernel_times(data, trace):
    ops = data["devices"]["/device:TPU:0"]["ops"]
    lo = trace.lo
    def total(pred):
        return sum(min(o[1] + o[2], trace.hi) - max(o[1], lo) for o in ops
                   if pred(o) and o[1] + o[2] > lo and o[1] < trace.hi) * 1e-9
    kern = total(lambda o: o[4] and "read_spec_products" in o[3])
    assert kern > 0
    assert trace.op_s(["read_spec_products"], mosaic=True) == pytest.approx(kern)
    # the ingest program runs no Pallas kernel, its XLA scatter dominates
    assert trace.op_s(["ingest_step"], mosaic=True) is None
    assert trace.module_s(["ingest_step"]) > trace.module_s(["read_spec_products"])
    top = trace.top_ops(3)
    assert all(name.startswith("jit_ingest_step_donated/") for name, _ in top)


def test_idle_gaps_name_the_open_span(data, trace):
    busy = raster(data)
    gaps, run = [], 0
    for b in busy:
        run = 0 if b else run + 1
        gaps.append(run)
    longest = max(gaps) * 1e-6
    label, secs = trace.idle_gaps(1)[0]
    assert secs == pytest.approx(longest, abs=5e-6)
    assert label == "bench.step"     # the device idles while the host digests


def test_work_counts_for_known_shapes():
    g, t = QVGA["tiers"]
    cells = 2 * 240 * 320
    wg, wt = peaks.product_work(QVGA, g), peaks.product_work(QVGA, t)
    assert wg["decay_bytes"] == 3 * 4 * cells          # SAE in, surface and STCF out
    assert wt["decay_bytes"] == 2 * 4 * cells
    assert wt["bytes"] == wt["decay_bytes"] + 2 * 4 * 240 * 320
    # 8x8 input, 2 channels, width 8, 3 classes, counted by hand
    assert peaks.head_flops(8, 8, 2, 3, 8) == 12800 + 3408 + 3408 + 192
    assert wg["flops"] == (cells * 9 + cells * 49
                           + peaks.head_flops(240, 320, 2, 10, 32))
    assert peaks.scatter_bytes(10, True) == 10 * 28
    assert peaks.least_time_s(197e12, 0, PEAK) == (1.0, "compute")
    with pytest.raises(KeyError):
        peaks.peak("TPU v99")


def test_roofline_and_mfu_readers(trace):
    steps = [harness.StepInfo(k, k * 0.01, 0.0, n_events=1_000_000,
                              due={"gesture": list(range(21)),
                                   "telemetry": list(range(43))})
             for k in (3, 4, 5)]
    ctx = harness.Context(trace, steps, QVGA, PEAK, trace.window_s)
    root = os.path.dirname(BENCH)
    kern = trace.op_s(["read_spec_products"], mosaic=True)
    cells = 2 * 240 * 320
    nbytes = 3 * (21 * 12 * cells + 43 * 8 * cells)
    roof = harness.load_reader(root, "decay_roofline")(ctx)
    assert roof == pytest.approx(100 * nbytes / 819e9 / kern)
    assert 0 < roof < 100
    g = peaks.product_work(QVGA, QVGA["tiers"][0])
    t = peaks.product_work(QVGA, QVGA["tiers"][1])
    per_step = max((21 * g["flops"] + 43 * t["flops"]) / 197e12,
                   (21 * g["bytes"] + 43 * t["bytes"] + 28e6) / 819e9)
    mfu = harness.load_reader(root, "step_mfu")(ctx)
    assert mfu == pytest.approx(100 * 3 * per_step / trace.window_s)
    offer = harness.load_reader(root, "host_offer_ms")(ctx)
    assert offer == pytest.approx(trace.span_s("bench.offer") / 3 * 1e3)
