"""The streaming runtime's profiler spans and counters (``serve.trace``),
read back from a real profiler trace, and the ingest ring's staging
sets under pipelining.

A toy two-tier runtime runs on the CPU under ``jax.profiler``; the
``.xplane.pb`` it writes is read with ``jax.profiler.ProfileData``.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.serve import spec as rs
from repro.serve import stream as st
from repro.serve.ts_engine import IngestRing, TSEngineConfig, TimeSurfaceEngine

H, W = 24, 32
TIERS = (st.QoSClass(tier="gesture", priority=0, period_s=0.01,
                     spec=rs.ReadoutSpec(surface=rs.surface(),
                                         stcf=rs.stcf())),
         st.QoSClass(tier="telemetry", priority=1, period_s=0.01))


class Recorder:
    """Forwards to the engine and keeps every ``read_many`` result."""

    def __init__(self, engine):
        self._engine = engine
        self.reads = []

    def read_many(self, specs, t_now=0.0, **kw):
        self.reads.append(self._engine.read_many(specs, t_now, **kw))
        return self.reads[-1]

    def __getattr__(self, name):
        return getattr(self._engine, name)


def run_toy(*, pipeline=True, mesh=None, cap=64, n_steps=4, seed=0,
            per_sensor=(20, 150)):
    """Two tiers of two sensors each, every sensor due every 10 ms.
    Returns the runtime, its records and the engine recorder."""
    rng = np.random.default_rng(seed)
    cfg = TSEngineConfig(h=H, w=W, n_slots=4, chunk_capacity=cap,
                         backend="interpret", block=(8, 16))
    eng = Recorder(TimeSurfaceEngine(cfg, mesh=mesh))
    rt = st.StreamRuntime(eng, st.StreamConfig(
        queue_capacity=1 << 14, deadline_s=0.01, pipeline=pipeline))
    sensors = [rt.connect(q) for q in TIERS for _ in range(2)]
    recs = []
    for k in range(1, n_steps + 1):
        for s in sensors:
            n = int(rng.integers(*per_sensor))
            t = np.sort((k - 1) * 0.01 + rng.random(n) * 0.01)
            s.offer((rng.integers(0, W, n), rng.integers(0, H, n), t,
                     rng.integers(0, 2, n)))
        recs.append(rt.step(k * 0.01))
    rt.flush()
    return rt, recs, eng


def serve_spans(directory):
    """``[name, start_ns, end_ns, {stat: value}]`` of every ``serve.``
    span in the trace under ``directory``, in start order."""
    path, = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append([ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)])
    return sorted(out, key=lambda s: s[1])


def traced(tmp_path, **kw):
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = run_toy(**kw)
    finally:
        jax.profiler.stop_trace()
    return got + (serve_spans(str(tmp_path)),)


def inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


@pytest.mark.parametrize("pipeline,mesh", [(True, None), (False, None),
                                           (True, "1-device")])
def test_spans_nest_per_deadline_and_count_the_work(tmp_path, pipeline, mesh):
    if mesh:
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(1)
    rt, recs, eng, spans = traced(tmp_path, pipeline=pipeline, mesh=mesh)
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    steps = by["serve.step"]
    assert [s[3]["deadline"] for s in steps] == list(range(len(recs)))
    for k, (step, rec) in enumerate(zip(steps, recs)):
        mine = {name: [s for s in group if inside(s, step)]
                for name, group in by.items()}
        sched, = mine["serve.schedule"]
        assert sched[3] == {"scheduled": len(rec.order),
                            "deferred": len(rec.deferred)}
        coal, = mine["serve.coalesce"]
        assert coal[3] == {"events": rec.n_events, "chunks": rec.n_chunks}
        assert len(mine["serve.ingest"]) == 2            # one per tier group
        assert sum(s[3]["rows"] for s in mine["serve.ingest"]) == rec.n_chunks
        read, = mine["serve.read"]
        assert read[3] == {"specs": 2}
        for ing in mine["serve.ingest"]:
            stage, = [s for s in mine["serve.stage"] if inside(s, ing)]
            upload, = [s for s in mine["serve.upload"] if inside(s, ing)]
            assert stage[2] <= upload[1]
            assert ing[3]["capacity"] == 64
            b = ing[3]["padded_rows"]
            assert b >= ing[3]["rows"] and b & (b - 1) == 0
    # the products of deadline k are delivered inside step k + 1 when
    # pipelined (the last by the flush, after every step), else inside k
    for name in ("serve.sync", "serve.readback", "serve.digest"):
        assert [s[3]["deadline"] for s in by[name]] == list(range(len(recs)))
        for s in by[name]:
            k = s[3]["deadline"] + int(pipeline)
            if k < len(steps):
                assert inside(s, steps[k])
            else:
                assert s[1] >= steps[-1][2]
    assert (sum(s[3]["events"] for s in by["serve.ingest"])
            == sum(r.n_events for r in recs) > 0)
    for k, read in enumerate(eng.reads):
        nbytes = sum(a.nbytes for prods in read.values() for a in prods.values())
        assert by["serve.readback"][k][3] == {"deadline": k, "bytes": nbytes}
        arrays = {id(a): a for prods in read.values() for a in prods.values()}
        leaves = sum(max(1, -(-a.nbytes // st.DIGEST_LEAF_BYTES))
                     for a in arrays.values())
        digest = dict(by["serve.digest"][k][3])
        assert digest.pop("workers") >= 1
        assert digest == {"deadline": k, "bytes": nbytes, "leaves": leaves}


def test_digests_do_not_depend_on_the_profiler(tmp_path):
    _, off, _ = run_toy(seed=3)
    _, on, _, spans = traced(tmp_path, seed=3)
    assert spans
    assert [r.digest for r in on] == [r.digest for r in off]


def test_ingest_ring_fresh_sets_where_device_put_aliases():
    """On the CPU ``device_put`` may alias a staging set, so the engine's
    ring hands out a fresh, zeroed set on every acquire."""
    eng = TimeSurfaceEngine(TSEngineConfig(h=H, w=W, n_slots=2,
                                           chunk_capacity=8))
    assert jax.devices()[0].platform == "cpu" and not eng._ring.reuse
    ring = IngestRing(capacity=8, reuse=False)
    a = ring.acquire(2)
    IngestRing.fill_row(a, 1, 1, (np.array([5], np.int32),) * 4)
    b, c = ring.acquire(2), ring.acquire(2)
    assert a is not b and a is not c
    assert a["valid"][1, 0] and not b["valid"].any() and not c["valid"].any()


def test_pipelined_two_tiers_of_one_padded_size_replay_the_synchronous_run():
    """Two tier groups of one padded batch size per deadline (each sensor
    one chunk), pipelined: the staging set of deadline k's first group
    comes round again at deadline k + 1 while k's scatter may still be
    queued.  Every run digests as the unpipelined run does."""
    for seed in range(24):
        kw = dict(cap=2048, n_steps=10, seed=seed, per_sensor=(1024, 2049))
        _, piped, _ = run_toy(pipeline=True, **kw)
        _, sync, _ = run_toy(pipeline=False, **kw)
        assert [r.digest for r in piped] == [r.digest for r in sync], seed
