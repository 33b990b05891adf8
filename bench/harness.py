"""The benchmark harness: one cell, found by name, set up, measured and
checked.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``bench/configs/<config>.json`` (the path ``BENCHMARK.json`` names):
  sensor geometry, eDRAM transient, pool (sharded over
  ``mesh_devices`` chips where that key is above 1), deadline grid,
  tiers with their products, limits of the comparison;
* ``bench/traffic/<traffic>.json``: parameters of the one general
  generator in ``bench/traffic/generator.py``;
* ``bench/metrics/<metric>.py``: a ``read(ctx)`` returning the metric's
  value, or ``None`` when the trace holds nothing to read.

The run: traffic from the seed, the engine and its stream runtime over
the program's public API, head weights drawn from the seed on the
device, sensors connected in waves in the fleet's order
(``generator.fleet``), every ingest batch shape and both read programs
warmed, then a closed loop on the virtual 10 ms deadline
grid for ``seconds`` of wall time (offers in arrival granules, then
``runtime.step``; no sleeping), then ``flush``.  Once the window has
closed and device memory has been read, a seeded sample of the products
delivered in the window is compared with ``bench/reference.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import random
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import generator
import peaks
import reference as ref
import tracing

BENCH_DIR = "bench"
#: products compared per run, drawn from the seed among those captured
SAMPLES = 12


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ----------------------------------------------------------------------------
# finding a cell's files
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    root: str
    workload: dict
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def name(self) -> str:
        return self.workload["name"]


def load_cell(root: str, name: str) -> Cell:
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    wl = by_name[name]
    centry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = read_json(os.path.join(root, centry["file"]))
    mix = read_json(os.path.join(root, BENCH_DIR, "traffic",
                                 wl["traffic"] + ".json"))
    return Cell(root, wl, config, mix, bench["end_to_end"], bench["per_layer"])


def load_reader(root: str, metric: str) -> Callable:
    path = os.path.join(root, BENCH_DIR, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def import_program(root: str) -> None:
    """The ``repro`` package of this checkout, never another copy."""
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro

    if not os.path.abspath(repro.__file__).startswith(os.path.abspath(src) + os.sep):
        raise ImportError(f"imported repro from {repro.__file__}, not {src}")


def configure_jax(root: str, config: dict) -> str:
    """Persistent compile cache at the checkout's fixed ``.jax_cache``
    (every program cached, however quick), and the configuration's
    matmul precision.  Call before any program is traced."""
    cache = os.path.join(os.path.abspath(root), ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    from repro import platform as pf

    got = pf.use_compile_cache()
    jax.config.update("jax_compilation_cache_dir", got)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if config.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          config["matmul_precision"])
    return got


class CompileMeter:
    """Backend compiles and persistent-cache hits, from jax's monitoring
    events (the ``chip_smoke.py`` meter)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def programs(self) -> int:
        """Programs built or loaded so far (a new shape is one or the other)."""
        return self.compiles + self.cache_hits


def device_memory(n_devices: int) -> dict:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()[:n_devices]]
    return {"peak": max(s.get("peak_bytes_in_use", 0) for s in stats),
            "in_use": max(s.get("bytes_in_use", 0) for s in stats),
            "limit": min(s.get("bytes_limit", 0) for s in stats)}


# ----------------------------------------------------------------------------
# the deployment
# ----------------------------------------------------------------------------

class Tap:
    """Forwards everything to the engine and keeps its latest
    ``read_many`` result, whose host copies the runtime's digest leaves
    cached on the arrays: the harness samples delivered products from
    them without another transfer."""

    def __init__(self, engine):
        self._engine = engine
        self.last = None

    def read_many(self, specs, t_now=0.0, **kw):
        self.last = self._engine.read_many(specs, t_now, **kw)
        return self.last

    def __getattr__(self, name):
        return getattr(self._engine, name)


def period_of(config: dict, tier: dict) -> float:
    return tier["period_s"] or config["deadline_s"]


def build_specs(config: dict, head_key: str):
    from repro.serve import spec as rs

    def product(p):
        kind = p["kind"]
        if kind == "surface":
            return rs.surface()
        if kind == "stcf":
            return rs.stcf(radius=p.get("radius"))
        if kind == "count":
            return rs.count(n_bits=p.get("n_bits", 4))
        if kind == "classify":
            return rs.classify(inputs=tuple(p["inputs"]), weights=head_key,
                               n_classes=p["n_classes"], width=p["width"])
        raise ValueError(f"unknown product kind {kind!r}")

    return {t["tier"]: rs.ReadoutSpec(**{n: product(p) for n, p in
                                         t["products"].items()})
            for t in config["tiers"]}


def build_engine(config: dict, specs: dict, backend: Optional[str] = None):
    """The configuration's engine over its tiers' specs, its slot pool
    sharded over ``mesh_devices`` devices where that is above 1."""
    from repro.serve import ts_engine as te

    c = config
    ecfg = te.TSEngineConfig(
        h=c["h"], w=c["w"], polarities=c["polarities"],
        n_slots=c["n_slots"], chunk_capacity=c["chunk_capacity"],
        mode=c["mode"], cmem_f=c["cmem_f"], tau_tw=c["tau_tw"],
        stcf_radius=c["stcf_radius"], stcf_threshold=c["stcf_threshold"],
        backend=backend, specs=tuple(specs.values()))
    mesh = None
    if c.get("mesh_devices", 1) > 1:
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(c["mesh_devices"])
    return te.TimeSurfaceEngine(ecfg, mesh=mesh)


def head_product(config: dict):
    for tier in config["tiers"]:
        for prod in tier["products"].values():
            if prod["kind"] == "classify":
                return prod
    return None


class Feed:
    """One sensor's looping traffic, handed out in arrival order."""

    def __init__(self, traffic):
        self.tr = traffic
        self.t = traffic.t.astype(np.float64)
        self.loop = 0
        self.ptr = 0

    def _split(self, g: float, shift: float) -> int:
        """First index whose absolute time ``t + shift`` is not below
        ``g``, decided on the shifted values as ``SensorTraffic.before``
        decides it."""
        t, n = self.t, self.tr.n
        hi = int(np.searchsorted(t, g - shift, side="left"))
        while hi > 0 and t[hi - 1] + shift >= g:
            hi -= 1
        while hi < n and t[hi] + shift < g:
            hi += 1
        return hi

    def take(self, g: float):
        """Every event not yet taken with time ``< g``, or ``None``."""
        parts = []
        while self.tr.n:
            shift = self.loop * self.tr.segment_s
            hi = max(self.ptr, self._split(g, shift))
            if hi > self.ptr:
                sl = slice(self.ptr, hi)
                parts.append((self.tr.x[sl], self.tr.y[sl],
                              self.t[sl] + shift, self.tr.p[sl]))
                self.ptr = hi
            if self.ptr < self.tr.n or shift + self.tr.segment_s >= g:
                break
            self.loop += 1
            self.ptr = 0
        if not parts:
            return None
        if len(parts) == 1:
            return parts[0]
        return tuple(np.concatenate([q[i] for q in parts]) for i in range(4))


def batch_sizes(config: dict, traffic, n_steps: int,
                chip_of: Optional[List[int]] = None) -> List[int]:
    """Every padded ingest batch, in rows per chip, the runtime can form
    over ``n_steps`` deadlines: each served sensor drains into
    ceil(n / chunk_capacity) chunks of at most ``queue_capacity``
    events, chunks of one tier share a dispatch, a sharded dispatch
    pads every chip to its fullest chip's rows (sensor ``i`` is on chip
    ``chip_of[i]``, all on one without it), and rows pad to powers of
    two.  Returns all powers of two up to the largest."""
    d, cap_q = config["deadline_s"], config["queue_capacity"]
    cap_c = config["chunk_capacity"]
    periods = {t["tier"]: period_of(config, t) for t in config["tiers"]}
    per_step: Dict[tuple, int] = {}
    deadlines = np.arange(1, n_steps + 1) * d
    for i, tr in enumerate(traffic):
        chip = chip_of[i] if chip_of else 0
        steps = ref.served_steps(periods[tr.tier], d, n_steps)
        ends = deadlines[np.asarray(steps) - 1]
        loops = np.floor(ends / tr.segment_s)
        before = loops * tr.n + np.searchsorted(
            tr.t.astype(np.float64), ends - loops * tr.segment_s, side="left")
        drained = np.minimum(np.diff(np.concatenate([[0], before])), cap_q)
        for k, n in zip(steps, drained):
            key = (k, tr.tier, chip)
            per_step[key] = per_step.get(key, 0) + -(-int(n) // cap_c)
    most = max(per_step.values(), default=1)
    out, b = [], 1
    while b < 2 * most:
        out.append(b)
        b *= 2
    return out


@dataclasses.dataclass
class StepInfo:
    k: int
    t: float
    started: float
    n_events: int = 0
    due: Dict[str, List[int]] = dataclasses.field(default_factory=dict)
    latency_s: float = math.nan

    @property
    def n_due(self) -> int:
        return sum(len(v) for v in self.due.values())


class Deployment:
    """The engine, its runtime, the connected sensors and their feeds."""

    def __init__(self, cell: Cell, seed: int, backend: Optional[str] = None,
                 tap: Callable = Tap):
        import jax

        from repro.serve import heads
        from repro.serve import stream as st

        c = self.config = cell.config
        t0 = time.perf_counter()
        self.traffic = generator.generate(cell.mix, c, seed)
        log(f"traffic: {len(self.traffic)} sensors, "
            f"{sum(t.n for t in self.traffic)} events per "
            f"{cell.mix['segment_s']} s segment, "
            f"{time.perf_counter() - t0:.3f} s")
        self.tiers = {t["tier"]: t for t in c["tiers"]}
        head_key = f"bench/{c['name']}"
        head = head_product(c)
        self.head_params = None
        if head is not None:
            self.head_params = ref.init_head(
                seed, len(head["inputs"]) * c["polarities"],
                head["n_classes"], head["width"])
            heads.register_head_params(head_key, self.head_params)
        self.specs = build_specs(c, head_key)
        self.engine = build_engine(c, self.specs, backend)
        self.tap = tap(self.engine)
        scfg = st.StreamConfig(policy=c["policy"],
                               queue_capacity=c["queue_capacity"],
                               deadline_s=c["deadline_s"], record_chunks=False)
        self.runtime = st.StreamRuntime(self.tap, scfg,
                                        self.specs[c["primary_tier"]])
        qos = {name: st.QoSClass(tier=name, priority=t["priority"],
                                 period_s=t["period_s"],
                                 slo_p99_s=t["slo_p99_s"],
                                 spec=self.specs[name])
               for name, t in self.tiers.items()}
        self.sensors = []
        wave = c.get("connect_wave") or len(self.traffic)
        for lo in range(0, len(self.traffic), wave):
            for tr in self.traffic[lo:lo + wave]:
                self.sensors.append(self.runtime.connect(qos[tr.tier]))
            jax.block_until_ready(self.engine.state)
        self.slot_of = [s.slot for s in self.sensors]
        per_chip = self.engine.n_slots_padded // c.get("mesh_devices", 1)
        self.chip_of = [s // per_chip for s in self.slot_of]
        self.sensor_of_slot = {s: i for i, s in enumerate(self.slot_of)}
        self.feeds = [Feed(tr) for tr in self.traffic]
        self.granules = int(cell.mix["arrival_granules"])
        self.k = 0
        firsts = [float(tr.t[0]) for tr in self.traffic if tr.n]
        self.epoch = float(math.floor(min(firsts))) if firsts else 0.0

    # -- set-up ---------------------------------------------------------------
    def warm_ingest(self, n_steps: int) -> List[int]:
        """Dispatch every ingest batch shape the traffic forms, with
        empty chunks (no state changes): ``b`` rows on the first slot's
        chip form the batch of ``b`` rows a chip."""
        import jax

        empty = (np.zeros(0, np.int32), np.zeros(0, np.int32),
                 np.zeros(0, np.float32), np.zeros(0, np.int32))
        sizes = batch_sizes(self.config, self.traffic, n_steps, self.chip_of)
        for b in sizes:
            self.engine.push_staged([(self.slot_of[0], empty)] * b)
        jax.block_until_ready(self.engine.state)
        return sizes

    # -- one deadline ---------------------------------------------------------
    def arrivals(self, k: int) -> List[list]:
        """Deadline ``k``'s events: per arrival granule, per sensor, the
        events with times up to the granule (``None`` where none)."""
        d = self.config["deadline_s"]
        out = []
        for j in range(1, self.granules + 1):
            g = k * d if j == self.granules else (k - 1) * d + j * d / self.granules
            out.append([feed.take(g) for feed in self.feeds])
        return out

    def offer(self, arrivals: List[list]) -> int:
        """Hand one deadline's arrivals to the sensors' queues."""
        n = 0
        for granule in arrivals:
            for sensor, ev in zip(self.sensors, granule):
                if ev is not None:
                    sensor.offer(ev)
                    n += len(ev[0])
        return n

    def due(self, rec) -> Dict[str, List[int]]:
        out: Dict[str, List[int]] = {}
        for slot, tier, _ in rec.order:
            out.setdefault(tier, []).append(self.sensor_of_slot[slot])
        return out


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


# ----------------------------------------------------------------------------
# the measured window
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    steps: List[StepInfo]
    samples: List[dict]
    wall_s: float
    offered: int
    ingested: int
    dropped: int
    programs: int


def _capture(dep: Deployment, read, info: StepInfo, rng) -> List[dict]:
    """Host copies of one due sensor per tier from a synced read."""
    out = []
    for tier, sensors in sorted(info.due.items()):
        i = rng.choice(sensors)
        slot = dep.slot_of[i]
        prods = read[dep.specs[tier]]
        out.append({"k": info.k, "t": info.t, "sensor": i, "tier": tier,
                    "chip": dep.chip_of[i],
                    "products": {n: np.array(np.asarray(a)[slot])
                                 for n, a in prods.items()}})
    return out


def run_window(dep: Deployment, seconds: float, meter: CompileMeter,
               rng: random.Random) -> Window:
    rt = dep.runtime
    c0 = rt.counters()
    p0 = meter.programs()
    steps: List[StepInfo] = []
    samples: List[dict] = []
    offered = 0
    pending = None          # (info, read) of the step awaiting its sync
    d = dep.config["deadline_s"]
    w0 = time.perf_counter()
    with annotate("bench.window"):
        while time.perf_counter() - w0 < seconds:
            dep.k += 1
            with annotate("bench.arrivals"):
                arrivals = dep.arrivals(dep.k)
            with annotate("bench.offer"):
                offered += dep.offer(arrivals)
            info = StepInfo(dep.k, dep.k * d, time.perf_counter())
            with annotate("bench.step"):
                rec = rt.step(dep.k * d)
            back = time.perf_counter()
            info.n_events = rec.n_events
            info.due = dep.due(rec)
            if pending is not None:
                p_info, p_read = pending
                p_info.latency_s = back - p_info.started
                with annotate("bench.capture"):
                    samples += _capture(dep, p_read, p_info, rng)
            pending = (info, dep.tap.last)
            steps.append(info)
        with annotate("bench.flush"):
            rt.flush()
        back = time.perf_counter()
    if pending is not None:
        p_info, p_read = pending
        p_info.latency_s = back - p_info.started
        samples += _capture(dep, p_read, p_info, rng)
    c1 = rt.counters()
    return Window(steps, samples, back - w0, offered,
                  c1["ingested"] - c0["ingested"], c1["dropped"] - c0["dropped"],
                  meter.programs() - p0)


# ----------------------------------------------------------------------------
# correctness
# ----------------------------------------------------------------------------

def pick_samples(samples: List[dict], seed: int, n: int = SAMPLES) -> List[dict]:
    """A seeded draw of ``n`` captured products, spread over the tiers
    and, within each, over the chips that hold them, always holding the
    window's last deadline (the longest history)."""
    if not samples:
        return []
    last_k = max(s["k"] for s in samples)
    keep = [s for s in samples if s["k"] == last_k]
    rest = [s for s in samples if s["k"] != last_k]
    random.Random(seed).shuffle(rest)
    groups: Dict[tuple, List[dict]] = {}
    for s in rest:
        groups.setdefault((s["tier"], s["chip"]), []).append(s)
    while len(keep) < n and any(groups.values()):
        for key in sorted(groups):
            if groups[key] and len(keep) < n:
                keep.append(groups[key].pop())
    return keep


def reference_readings(cell: Cell, dep_view: dict, samples: List[dict],
                       dtype) -> List[Dict[str, float]]:
    """Compare each sample with the reference computed in ``dtype``."""
    c = cell.config
    d = c["deadline_s"]
    out = []
    for s in samples:
        tier = dep_view["tiers"][s["tier"]]
        tr = dep_view["traffic"][s["sensor"]]
        drains = ref.served_steps(period_of(c, tier), d, s["k"])
        deadlines = [k * d for k in drains]
        held = ref.held_events(tr.before(deadlines[-1]), deadlines,
                               c["queue_capacity"])
        want = ref.products(c, tier, held, s["t"], dep_view["epoch"],
                            dep_view["head_params"], dtype)
        out.append(ref.compare(c, tier, s["products"], want))
    return out


def schedule_mismatch(cell: Cell, dep_view: dict,
                      steps: List[StepInfo]) -> int:
    """Deadlines of the window at which the served sensors differ from
    the sensors whose period came due."""
    c = cell.config
    if not steps:
        return 0
    last = steps[-1].k
    due_at: Dict[int, set] = {}
    for i, tr in enumerate(dep_view["traffic"]):
        tier = dep_view["tiers"][tr.tier]
        for k in ref.served_steps(period_of(c, tier), c["deadline_s"], last):
            due_at.setdefault(k, set()).add(i)
    bad = 0
    for info in steps:
        got = {i for v in info.due.values() for i in v}
        bad += got != due_at.get(info.k, set())
    return bad


# ----------------------------------------------------------------------------
# a run
# ----------------------------------------------------------------------------

def percentile(values, q) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, backend: Optional[str] = None,
             n_devices: int = 1, tap: Callable = Tap,
             control: bool = False) -> dict:
    """Set up, measure and check one cell.  Returns ``correct``,
    ``attempted``, ``failed``, the end-to-end metrics (``e2e``), the
    numbers compared with their limits (``checks``), device memory and,
    with ``trace``, the per-layer metrics; with ``control`` also the
    bfloat16 reference's readings on the same products."""
    import jax
    import jax.numpy as jnp

    cell = load_cell(root, workload)
    c = cell.config
    d = c["deadline_s"]
    meter = CompileMeter()
    dep = Deployment(cell, seed, backend=backend, tap=tap)
    # two real deadlines read every spec once; the batch shapes are
    # those of the first segment loops, where the schedule settles
    warm_steps = 2
    cycle = max(1, round(cell.mix["segment_s"] / d))
    sizes = dep.warm_ingest(warm_steps + 4 * cycle)
    for _ in range(warm_steps):
        dep.k += 1
        dep.offer(dep.arrivals(dep.k))
        dep.runtime.step(dep.k * d)
    dep.runtime.flush()
    jax.block_until_ready(dep.engine.state)
    # what set-up built stays: the window's collections scan only what
    # the window allocates, as a long-running service arranges it
    gc.collect()
    gc.freeze()
    mem_setup = device_memory(n_devices)
    setup_s = time.perf_counter() - t_start
    log(f"set-up: {setup_s:.3f} s; ingest batches warmed {sizes}; "
        f"{meter.compiles} compiles ({meter.seconds:.3f} s), "
        f"{meter.cache_hits} cache hits; device peak "
        f"{mem_setup['peak'] / 2**30:.3f} GiB, in use "
        f"{mem_setup['in_use'] / 2**30:.3f} GiB of "
        f"{mem_setup['limit'] / 2**30:.3f} GiB")

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    win = run_window(dep, seconds, meter, random.Random(seed))
    if trace:
        jax.profiler.stop_trace()
    mem = device_memory(n_devices)
    log(f"window: {len(win.steps)} deadlines in {win.wall_s:.3f} s "
        f"(realtime_x {len(win.steps) * d / win.wall_s:.6f}); offered "
        f"{win.offered}, ingested {win.ingested}, dropped {win.dropped}; "
        f"compiles inside the window: {win.programs}; device peak "
        f"{mem['peak'] / 2**30:.3f} GiB")
    lat = [info.latency_s for info in win.steps for _ in range(info.n_due)]
    e2e = {"events_per_s": win.ingested / win.wall_s,
           "deadline_p95_ms": float(np.percentile(lat, 95)) * 1e3,
           "peak_hbm_gib": mem["peak"] / 2**30,
           "setup_s": setup_s}

    pool = jax.tree_util.tree_leaves(dep.engine.state)[0]
    sharded_over = len(pool.sharding.device_set)
    # the program's state goes before the reference runs
    view = {"traffic": dep.traffic, "tiers": dep.tiers, "epoch": dep.epoch,
            "head_params": dep.head_params}
    del dep
    gc.unfreeze()
    gc.collect()
    chosen = pick_samples(win.samples, seed)
    t0 = time.perf_counter()
    numbers = ref.fold(reference_readings(cell, view, chosen, jnp.float32))
    numbers["schedule_mismatch"] = schedule_mismatch(cell, view, win.steps)
    correct, checks = ref.verdict(numbers, c.get("limits", {}))
    log(f"reference: {len(chosen)} products compared in "
        f"{time.perf_counter() - t0:.3f} s")
    result = {"correct": bool(correct and chosen),
              "attempted": win.ingested + win.dropped, "failed": win.dropped,
              "e2e": e2e, "checks": checks, "numbers": numbers,
              "memory_peak_bytes": mem["peak"], "window_s": win.wall_s,
              "sharded_over": sharded_over}
    if control:
        result["control"] = ref.fold(
            reference_readings(cell, view, chosen, jnp.bfloat16))
    if trace:
        result.update(per_layer(cell, win, tdir))
    return result


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets."""

    trace: tracing.Trace     # the traced window
    steps: List[StepInfo]    # its deadlines
    config: dict
    peaks: Optional[Dict[str, float]]   # one chip's peak rates
    window_s: float          # wall seconds of the window
    chips: int               # the cell's chips (``BENCHMARK.json``)


def per_layer(cell: Cell, win: Window, tdir: str) -> dict:
    import shutil

    import jax

    try:
        tr = tracing.Trace(tracing.load(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    kind = jax.devices()[0].device_kind
    ctx = Context(tr, win.steps, cell.config, peaks.PEAKS.get(kind),
                  win.wall_s, cell.workload["chips"])
    metrics = {}
    for m in cell.per_layer:
        v = load_reader(cell.root, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return {"per_layer": metrics, "busy_s": tr.busy_s(),
            "trace_window_s": tr.window_s,
            "breakdown": {"device_ops": tr.top_ops(10),
                          "idle_gaps": tr.idle_gaps(10)}}
