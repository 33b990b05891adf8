"""The trace reduction and the work counts behind the per-layer metrics,
on three deadlines cut from a chip trace (``data/``)."""
import itertools
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import harness
import peaks
import programs
import tracing
from conftest import BENCH, HERE

DATA = os.path.join(HERE, "data", "trace_qvga_3_deadlines.json")
X4 = os.path.join(HERE, "data", "trace_qvga_x4_3_deadlines.json")
QVGA = json.load(open(os.path.join(BENCH, "configs", "isc-qvga-tiered.json")))
QVGA_X4 = json.load(open(os.path.join(BENCH, "configs",
                                      "isc-qvga-tiered-x4.json")))
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
    ctx = harness.Context(trace, [], QVGA, PEAK, trace.window_s, 1)
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


def fleet_steps(copies=1):
    """Deadlines 3-5 of the recorded window with every sensor of
    ``copies`` one-chip fleets due."""
    return [harness.StepInfo(k, k * 0.01, 0.0, n_events=copies * 1_000_000,
                             due={"gesture": list(range(21 * copies)),
                                  "telemetry": list(range(43 * copies))})
            for k in (3, 4, 5)]


def readings(data, steps, chips):
    tr = tracing.Trace(data)
    ctx = harness.Context(tr, steps, QVGA, PEAK, tr.window_s, chips)
    root = os.path.dirname(BENCH)
    return {m: harness.load_reader(root, m)(ctx)
            for m in ("ingest_device_ms", "read_device_ms", "decay_roofline",
                      "step_mfu", "device_idle_share")}


def test_one_chip_readings_pinned(data):
    """The readers read the one-chip trace as they did before they
    learnt the sharded programs and per-chip units, bit for bit."""
    got = readings(data, fleet_steps(), 1)
    assert got == {"ingest_device_ms": 0.129401058 / 3 * 1e3,
                   "read_device_ms": 0.032828880000000005 / 3 * 1e3,
                   "decay_roofline": 1.6906222745560127,
                   "step_mfu": 0.03799789061335774,
                   "device_idle_share": 88.47268778187642}


def four_planes(data):
    """The one-chip trace as four chips that each ran it, with the
    programs renamed as the sharded plan (``_ShardPlan``) names them
    (the two reads of a deadline: the telemetry spec with counts, the
    gesture spec without)."""
    plane = data["devices"]["/device:TPU:0"]
    reads = itertools.cycle(["jit_local_with_counts", "jit_local_no_counts"])

    def rename(name):
        if name == "jit_read_spec_products":
            return next(reads)
        return {"jit_ingest_step_donated": "jit_local_ingest"}.get(name, name)

    mods = [[rename(m[0])] + m[1:] for m in plane["modules"]]
    ops = []
    for o in plane["ops"]:
        owner = next((m for m in mods if m[1] <= o[1] < m[1] + m[2]), None)
        ops.append(o[:3] + [owner[0] if owner else o[3]] + o[4:])
    return dict(data, devices={f"/device:TPU:{i}": {"ops": ops, "modules": mods}
                               for i in range(4)})


def test_four_chips_read_per_chip(data):
    one = readings(data, fleet_steps(), 1)
    x4 = four_planes(data)
    assert {m[0] for m in x4["devices"]["/device:TPU:3"]["modules"]} == {
        "jit_local_ingest", "jit_local_with_counts", "jit_local_no_counts",
        "jit_convert_element_type"}
    same = readings(x4, fleet_steps(), 4)
    for m in ("ingest_device_ms", "read_device_ms", "device_idle_share"):
        assert same[m] == pytest.approx(one[m], rel=1e-12), m
    # the same due sensors on four times the chips: a quarter of the share
    for m in ("step_mfu", "decay_roofline"):
        assert same[m] == pytest.approx(one[m] / 4, rel=1e-12), m
    # four times the fleet on four times the chips: the same shares
    fleet = readings(x4, fleet_steps(4), 4)
    assert fleet["decay_roofline"] == pytest.approx(one["decay_roofline"],
                                                    rel=1e-12)
    assert fleet["step_mfu"] == pytest.approx(one["step_mfu"], rel=1e-12)


def test_roofline_and_mfu_readers(trace):
    steps = fleet_steps()
    ctx = harness.Context(trace, steps, QVGA, PEAK, trace.window_s, 1)
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


#: the program lists of the two device-time readers
READERS = {"ingest_device_ms": programs.INGEST,
           "read_device_ms": programs.READ}


def counted_by(module):
    """Which of the two device-time readers counts an XLA program."""
    return [m for m, pats in READERS.items()
            if re.search("|".join(pats), module)]


SHARDED_NAMES = """
import json, sys
sys.path[:0] = [sys.argv[1] + "/bench", sys.argv[1] + "/bench/traffic"]
import harness
cell = harness.load_cell(sys.argv[1], "qvga_tiered_x4")
harness.import_program(sys.argv[1])
specs = harness.build_specs(cell.config, "unused")
engine = harness.build_engine(cell.config, specs)
plan = engine._plan

def jitted(fn):
    # a spec reader without counts wraps its jitted program in a lambda
    if hasattr(fn, "lower"):
        return fn
    return next(c.cell_contents for c in fn.__closure__
                if hasattr(c.cell_contents, "lower"))

from repro.serve import spec as rs
read = [jitted(plan.spec_reader(sp)).__name__ for sp in specs.values()]
compiled = [rs.compile_spec(sp, engine.cfg) for sp in specs.values()]
read += [plan.head_reader(c).__name__ for c in compiled if c.has_heads]
print(json.dumps({"ingest": [plan.ingest.__name__], "read": read}))
"""


def test_sharded_programs_match_the_readers():
    """Every program the four-chip cell's stream path jits, named as
    the profiler names it (``jit_`` and the function's name), is counted
    by the one reader of its path: a rename cannot drop a metric."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", SHARDED_NAMES,
                          os.path.dirname(BENCH)], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    names = json.loads(out.stdout.strip().splitlines()[-1])
    assert names["ingest"] == ["local_ingest"]
    assert sorted(names["read"]) == ["local", "local_no_counts",
                                     "local_with_counts"]
    for path, metric in (("ingest", "ingest_device_ms"),
                         ("read", "read_device_ms")):
        for name in names[path]:
            assert counted_by("jit_" + name) == [metric], name


#: programs of a traced window that neither device-time reader counts
#: (PERF.md section 3): the scalar conversion of ``t_now`` before a read
NEITHER = {"jit_convert_element_type"}


def window_programs(tr):
    return {m[0] for dev in tr.devices
            for m in tr.data["devices"][dev]["modules"]
            if m[1] < tr.hi and m[1] + m[2] > tr.lo}


@pytest.mark.parametrize("path", [DATA, X4], ids=["one_chip", "x4"])
def test_every_program_is_counted_once(path):
    tr = tracing.Trace(json.load(open(path)))
    for name in window_programs(tr):
        assert len(counted_by(name)) == (name not in NEITHER), name


def test_four_chip_trace_per_chip():
    """The recorded four-chip window: device time per deadline and chip
    is the hand sum over the four planes divided by four."""
    tr = tracing.Trace(json.load(open(X4)))
    assert tr.devices == [f"/device:TPU:{i}" for i in range(4)]
    assert {"jit_local_ingest", "jit_local_with_counts",
            "jit_local_no_counts"} <= window_programs(tr)

    def hand(names):
        return sum(min(s + d, tr.hi) - max(s, tr.lo) for dev in tr.devices
                   for n, s, d in tr.data["devices"][dev]["modules"]
                   if n in names and s < tr.hi and s + d > tr.lo) * 1e-9

    steps = fleet_steps(4)
    ctx = harness.Context(tr, steps, QVGA_X4, PEAK, tr.window_s, 4)
    root = os.path.dirname(BENCH)
    got = {m: harness.load_reader(root, m)(ctx)
           for m in ("ingest_device_ms", "read_device_ms", "decay_roofline",
                     "step_mfu", "device_idle_share")}
    assert got["ingest_device_ms"] == pytest.approx(
        hand({"jit_local_ingest"}) / 4 / 3 * 1e3, rel=1e-12)
    assert got["read_device_ms"] == pytest.approx(
        hand({"jit_local_with_counts", "jit_local_no_counts"}) / 4 / 3 * 1e3,
        rel=1e-12)
    for m in ("decay_roofline", "step_mfu", "device_idle_share"):
        assert 0 < got[m] < 100, m
