"""Unit tests for the real-time streaming runtime + replay harness.

Everything here runs under the virtual clock — which events are
accepted, dropped, coalesced into which chunk of which deadline is a
pure function of event timestamps and the deadline grid, so every
assertion below is exact (drop *counts*, chunk *sizes*, bitwise
surfaces), not statistical.
"""
import hashlib

import numpy as np
import pytest

from repro.events import pipeline
from repro.events import replay as rp
from repro.events import synthetic as syn
from repro.serve import spec as rs
from repro.serve import stream
from repro.serve.stream import StreamConfig, StreamRuntime
from repro.serve.ts_engine import TSEngineConfig, TimeSurfaceEngine

H, W = 24, 32
CAP = 64


def make_cfg(n_slots=4):
    return TSEngineConfig(h=H, w=W, n_slots=n_slots, chunk_capacity=CAP,
                          backend="interpret", block=(8, 16))


def make_engine(n_slots=4):
    return TimeSurfaceEngine(make_cfg(n_slots))


def events(rng, n, t_lo=0.0, t_hi=0.06):
    t = np.sort(t_lo + rng.random(n).astype(np.float32) * (t_hi - t_lo))
    return syn.EventStream(
        x=rng.integers(0, W, n).astype(np.int32),
        y=rng.integers(0, H, n).astype(np.int32),
        t=t.astype(np.float32),
        p=rng.integers(0, 2, n).astype(np.int32),
        is_signal=np.ones(n, bool), h=H, w=W,
    )


def surface_of(engine_events, t_read):
    """Fresh-engine oracle: push ``engine_events`` on slot 0, read."""
    eng = make_engine()
    cam = eng.attach()
    if engine_events.n:
        cam.push(engine_events)
    return np.asarray(eng.read(rs.SURFACE_SPEC, t_read)["surface"])


# ---------------------------------------------------------------------------
# coalescing + deadlines
# ---------------------------------------------------------------------------

def test_coalescing_boundaries():
    """A queue drains into ceil(n/capacity) chunks: full, full, remainder."""
    rt = StreamRuntime(make_engine(), StreamConfig(queue_capacity=1 << 12))
    cam = rt.connect()
    ev = events(np.random.default_rng(0), 2 * CAP + 5)
    assert cam.offer(ev) == 2 * CAP + 5
    rec = rt.step(0.06)
    assert rec.n_events == 2 * CAP + 5
    assert rec.n_chunks == 3
    sizes = [len(seg[0]) for _, seg in rec.chunks]
    assert sizes == [CAP, CAP, 5]
    assert all(slot == cam.slot for slot, _ in rec.chunks)
    got = rt.flush()["surface"]
    assert (np.asarray(got)[cam.slot] == surface_of(ev, 0.06)[0]).all()
    assert cam.queued == 0 and cam.ingested == 2 * CAP + 5


def test_deadline_alignment():
    """Each deadline's chunks hold exactly the events of its window."""
    rng = np.random.default_rng(1)
    stream = events(rng, 300, t_lo=0.0, t_hi=0.03)
    eng = make_engine()
    report = rp.replay(
        eng, [rp.SensorFeed(stream=stream)],
        StreamConfig(policy="block", queue_capacity=1 << 12,
                     deadline_s=0.01),
        arrival_substeps=4,
    )
    d = 0.01
    per_step = [e.n_events for kind, e in report.log if kind == "step"]
    want = [
        int(((stream.t >= np.float32((k - 1) * d))
             & (stream.t < np.float32(k * d))).sum())
        for k in range(1, len(per_step) + 1)
    ]
    assert per_step == want
    assert sum(per_step) == stream.n
    assert report.ingested == stream.n and report.dropped == 0


def test_step_reads_at_deadline_even_when_idle():
    """Deadlines with no traffic still produce a frame (and a digest)."""
    rt = StreamRuntime(make_engine(), StreamConfig())
    rt.connect()
    rec = rt.step(0.02)
    assert rec.n_events == 0 and rec.n_chunks == 0
    assert rt.flush() is not None
    assert rec.digest  # filled at sync


# ---------------------------------------------------------------------------
# overload policies: exact drop accounting
# ---------------------------------------------------------------------------

def test_policy_block_backpressure():
    rt = StreamRuntime(
        make_engine(), StreamConfig(policy="block", queue_capacity=10))
    cam = rt.connect()
    ev = events(np.random.default_rng(2), 25)
    assert cam.offer(ev) == 10          # only what fits is consumed
    assert cam.queued == 10 and cam.refused == 15 and cam.dropped == 0
    assert cam.offer(ev.take(slice(10, 25))) == 0   # full: nothing enters
    rt.step(0.06)
    assert cam.queued == 0
    assert cam.offer(ev.take(slice(10, 25))) == 10  # drained: room again
    rt.step(0.07)
    rt.flush()
    assert cam.ingested == 20 and cam.dropped == 0
    # the engine saw exactly the first 20 events, in order
    got = np.asarray(rt.engine.state.surfaces.n_events)[cam.slot]
    assert got == 20


def test_policy_drop_newest():
    rt = StreamRuntime(
        make_engine(), StreamConfig(policy="drop_newest", queue_capacity=10))
    cam = rt.connect()
    ev = events(np.random.default_rng(3), 25)
    assert cam.offer(ev) == 25          # everything consumed...
    assert cam.accepted == 10 and cam.dropped == 15   # ...overflow discarded
    rt.step(0.06)
    got = rt.flush()["surface"]
    want = surface_of(ev.take(slice(0, 10)), 0.06)    # the OLDEST survive
    assert (np.asarray(got)[cam.slot] == want[0]).all()


def test_policy_drop_oldest():
    rt = StreamRuntime(
        make_engine(), StreamConfig(policy="drop_oldest", queue_capacity=10))
    cam = rt.connect()
    ev = events(np.random.default_rng(4), 25)
    assert cam.offer(ev) == 25
    assert cam.accepted == 25 and cam.dropped == 15 and cam.queued == 10
    rt.step(0.06)
    got = rt.flush()["surface"]
    want = surface_of(ev.take(slice(15, 25)), 0.06)   # the NEWEST survive
    assert (np.asarray(got)[cam.slot] == want[0]).all()


def test_drop_oldest_eviction_spans_segments():
    """Eviction walks whole and partial queued segments correctly."""
    rt = StreamRuntime(
        make_engine(), StreamConfig(policy="drop_oldest", queue_capacity=8))
    cam = rt.connect()
    rng = np.random.default_rng(5)
    ev = events(rng, 12)
    for lo in (0, 3, 6, 9):             # four 3-event offers
        cam.offer(ev.take(slice(lo, lo + 3)))
    assert cam.queued == 8 and cam.dropped == 4
    rt.step(0.06)
    got = rt.flush()["surface"]
    want = surface_of(ev.take(slice(4, 12)), 0.06)    # last 8 survive
    assert (np.asarray(got)[cam.slot] == want[0]).all()


def test_counter_conservation():
    """accepted == ingested + dropped-evictions + discarded + queued."""
    rt = StreamRuntime(
        make_engine(), StreamConfig(policy="drop_oldest", queue_capacity=32))
    cams = [rt.connect() for _ in range(3)]
    rng = np.random.default_rng(6)
    for i, cam in enumerate(cams):
        cam.offer(events(rng, 50 + 20 * i))
    rt.step(0.06)
    cams[0].offer(events(rng, 40))
    rt.disconnect(cams[0])              # queued events -> discarded
    rt.step(0.07)
    rt.flush()
    c = rt.counters()
    assert c["accepted"] == (c["ingested"] + c["dropped"]
                             + c["discarded"] + c["queued"])
    assert c["discarded"] == 32         # full queue at disconnect


# ---------------------------------------------------------------------------
# churn + lifecycle
# ---------------------------------------------------------------------------

def test_churn_midrun_replay_oracle():
    feeds = rp.mixed_scene_feeds(H, W, 0.06, 4, seed=1, churn=True)
    assert any(f.attach_t > 0 for f in feeds)
    assert any(f.detach_t is not None for f in feeds)
    cfg = make_cfg()
    report = rp.replay(
        TimeSurfaceEngine(cfg), feeds,
        StreamConfig(policy="drop_oldest", queue_capacity=256,
                     deadline_s=0.01),
    )
    n = rp.check_oracle(report, lambda: TimeSurfaceEngine(cfg))
    assert n == report.n_steps > 0
    kinds = [k for k, _ in report.log]
    assert kinds.count("attach") == 4 and kinds.count("detach") == 1


def test_disconnect_frees_slot_and_dead_sensor_raises():
    rt = StreamRuntime(make_engine(n_slots=2), StreamConfig())
    a, b = rt.connect(), rt.connect()
    with pytest.raises(RuntimeError):
        rt.connect()                    # pool full
    slot_a = a.slot
    rt.disconnect(a)
    with pytest.raises(RuntimeError):
        a.offer(events(np.random.default_rng(0), 4))
    with pytest.raises(RuntimeError):
        rt.disconnect(a)
    c = rt.connect()                    # slot reused
    assert c.slot == slot_a
    rt.disconnect(b)
    rt.disconnect(c)


# ---------------------------------------------------------------------------
# pipelining + determinism + oracle
# ---------------------------------------------------------------------------

def _replay_once(pipeline_on: bool, policy="block"):
    feeds = rp.mixed_scene_feeds(H, W, 0.05, 3, seed=2)
    cfg = make_cfg()
    return rp.replay(
        TimeSurfaceEngine(cfg), feeds,
        StreamConfig(policy=policy, queue_capacity=1 << 14,
                     deadline_s=0.01, pipeline=pipeline_on),
    )


def test_pipelined_bitwise_equals_synchronous():
    """Pipelining moves *when* syncs happen, never what is computed."""
    a = _replay_once(True)
    b = _replay_once(False)
    assert a.digests == b.digests
    assert (a.ingested, a.dropped, a.n_steps) == (
        b.ingested, b.dropped, b.n_steps)


def test_replay_deterministic():
    a = _replay_once(True, policy="drop_oldest")
    b = _replay_once(True, policy="drop_oldest")
    assert a.digests == b.digests
    assert (a.offered, a.accepted, a.ingested, a.dropped) == (
        b.offered, b.accepted, b.ingested, b.dropped)


def test_replay_report_fields():
    report = _replay_once(True)
    assert report.n_steps == len(report.digests) > 0
    assert report.events_per_sec > 0 and report.wall_s > 0
    assert report.latency_p50_us is not None
    assert report.latency_p50_us <= report.latency_p99_us
    assert report.drop_rate == 0.0      # block + huge queue
    assert "Meps" in report.summary()


def test_offer_copies_producer_buffers():
    """Producers may reuse/mutate their buffers right after offer()."""
    rt = StreamRuntime(make_engine(), StreamConfig())
    cam = rt.connect()
    ev = events(np.random.default_rng(10), 30)
    x, y, t, p = ev.x.copy(), ev.y.copy(), ev.t.copy(), ev.p.copy()
    cam.offer((x, y, t, p))
    x[:], y[:], t[:], p[:] = 0, 0, 9.9, 0    # producer reuses its buffer
    rec = rt.step(0.06)
    got = rt.flush()["surface"]
    assert (np.asarray(got)[cam.slot] == surface_of(ev, 0.06)[0]).all()
    # the action log must hold the original values too (oracle input)
    _, (lx, ly, lt, lp) = rec.chunks[0]
    np.testing.assert_array_equal(lt, ev.t)


def test_log_trimming_bounds_retention():
    """Beyond max_record_steps the oldest step entries are trimmed (and
    counted); a trimmed replay refuses the oracle gate with a clear
    error instead of silently diverging."""
    rt = StreamRuntime(
        make_engine(),
        StreamConfig(max_record_steps=3, queue_capacity=1 << 12))
    cam = rt.connect()
    rng = np.random.default_rng(9)
    for k in range(6):
        cam.offer(events(rng, 10))
        rt.step(0.01 * (k + 1))
    rt.flush()
    steps = [e for kind, e in rt.log if kind == "step"]
    assert len(steps) == 3 and rt.log_trimmed_steps == 3
    assert rt.n_steps == 6 and rt.stats()["log_trimmed_steps"] == 3
    assert any(kind == "attach" for kind, _ in rt.log)   # lifecycle kept

    cfg = make_cfg()
    report = rp.replay(
        TimeSurfaceEngine(cfg), rp.mixed_scene_feeds(H, W, 0.04, 2, seed=9),
        StreamConfig(queue_capacity=1 << 14, deadline_s=0.01,
                     max_record_steps=2),
    )
    with pytest.raises(ValueError, match="max_record_steps"):
        rp.check_oracle(report, lambda: TimeSurfaceEngine(cfg))


def test_paced_replay_same_results():
    """Wall-clock pacing (speed > 0) slows the loop, never the results."""
    import time

    feeds = rp.mixed_scene_feeds(H, W, 0.04, 2, seed=8)
    cfg = make_cfg()
    scfg = StreamConfig(queue_capacity=1 << 14, deadline_s=0.01)
    fast = rp.replay(TimeSurfaceEngine(cfg), feeds, scfg)
    t0 = time.perf_counter()
    paced = rp.replay(TimeSurfaceEngine(cfg),
                      rp.mixed_scene_feeds(H, W, 0.04, 2, seed=8),
                      scfg, speed=2.0)   # 2x real time: >= ~20ms of pacing
    wall = time.perf_counter() - t0
    assert paced.digests == fast.digests
    assert (paced.ingested, paced.dropped) == (fast.ingested, fast.dropped)
    assert wall >= 0.04 / 2.0 * 0.5      # pacing actually slept


def test_oracle_needs_recorded_chunks():
    feeds = rp.mixed_scene_feeds(H, W, 0.03, 2, seed=3)
    cfg = make_cfg()
    report = rp.replay(
        TimeSurfaceEngine(cfg), feeds,
        StreamConfig(queue_capacity=1 << 14, deadline_s=0.01,
                     record_chunks=False),
    )
    with pytest.raises(ValueError, match="record_chunks"):
        rp.check_oracle(report, lambda: TimeSurfaceEngine(cfg))


def test_offer_accepts_aer_words_and_tuples():
    from repro.events import aer

    rt = StreamRuntime(make_engine(), StreamConfig())
    cam = rt.connect()
    ev = events(np.random.default_rng(7), 20)
    assert cam.offer(aer.pack(ev)) == 20            # packed uint64 words
    assert cam.offer((ev.x, ev.y, ev.t, ev.p)) == 20  # raw arrays
    rec = rt.step(0.06)
    rt.flush()
    assert rec.n_events == 40


def test_composed_spec_stream():
    """The runtime serves composed specs; oracle gate covers every product."""
    spec = rs.ReadoutSpec(surface=rs.surface(), stcf=rs.stcf(),
                          count=rs.count(4))
    cfg = TSEngineConfig(h=H, w=W, n_slots=2, chunk_capacity=CAP,
                         backend="interpret", block=(8, 16), specs=(spec,))
    feeds = rp.mixed_scene_feeds(H, W, 0.04, 2, seed=4)
    report = rp.replay(
        TimeSurfaceEngine(cfg), feeds,
        StreamConfig(queue_capacity=1 << 14, deadline_s=0.01),
        spec,
    )
    rp.check_oracle(report, lambda: TimeSurfaceEngine(cfg), spec)


def test_stream_classify_tier_end_to_end():
    """The PR-7 acceptance gate: a gesture tier carrying a
    Classify-bearing spec streams model logits through the runtime —
    digest-chained and bitwise-reproduced by the replay oracle, and
    bitwise equal to the standalone frontend + ``cnn_apply`` over the
    same step's served surfaces — single-device and on a 1-device
    mesh."""
    import dataclasses

    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.models import cnn
    from repro.models.frontends import ts_stack_frontend
    from repro.serve import heads as heads_mod

    head = rs.classify(n_classes=4, width=8)
    head_spec = rs.ReadoutSpec(surface=rs.surface(), logits=head)

    def tiered_feeds():
        feeds = rp.mixed_scene_feeds(H, W, 0.04, 3, seed=21, tiered=True)
        for f in feeds:
            if f.qos.tier == "gesture":
                f.qos = dataclasses.replace(f.qos, spec=head_spec)
        return feeds

    assert any(f.qos.spec == head_spec for f in tiered_feeds())
    cfg = make_cfg()
    scfg = StreamConfig(policy="drop_oldest", queue_capacity=256,
                        deadline_s=0.01)
    eng = TimeSurfaceEngine(cfg)
    report = rp.replay(eng, tiered_feeds(), scfg)
    # (a) logits are digest-chained per deadline and replay bitwise
    n = rp.check_oracle(report, lambda: TimeSurfaceEngine(cfg))
    assert n == report.n_steps > 0
    # (b) the streamed logits equal the standalone head over the same
    # final-state surfaces (the engine retains the last step's state)
    t_last = report.n_steps * scfg.deadline_s
    out = eng.read(head_spec, t_last)
    params = heads_mod.resolve_head_params(head, cfg)
    want = jax.jit(
        lambda p, s: cnn.cnn_apply(p, ts_stack_frontend([s]))
    )(params, out["surface"])
    assert (np.asarray(out["logits"]) == np.asarray(want)).all()
    # same bits over a 1-device mesh, per-deadline
    mesh = make_host_mesh(1)
    sharded = rp.replay(TimeSurfaceEngine(cfg, mesh=mesh), tiered_feeds(),
                        scfg)
    assert sharded.digests == report.digests
    rp.check_oracle(sharded, lambda: TimeSurfaceEngine(cfg, mesh=mesh))


def test_stream_mesh_single_device():
    """The runtime over a 1-device mesh engine: same bits as unsharded."""
    from repro.launch.mesh import make_host_mesh

    cfg = make_cfg()
    feeds = rp.mixed_scene_feeds(H, W, 0.04, 2, seed=6)
    scfg = StreamConfig(queue_capacity=1 << 14, deadline_s=0.01)
    plain = rp.replay(TimeSurfaceEngine(cfg), feeds, scfg)
    mesh = make_host_mesh(1)
    sharded = rp.replay(TimeSurfaceEngine(cfg, mesh=mesh),
                        rp.mixed_scene_feeds(H, W, 0.04, 2, seed=6), scfg)
    assert plain.digests == sharded.digests
    rp.check_oracle(sharded, lambda: TimeSurfaceEngine(cfg, mesh=mesh))


# the multi-device sweep runs in a subprocess so the main test process
# stays single-device (same pattern as test_serve_sharded's slow sweep)
_MESH_SWEEP = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import numpy as np
from repro.events import replay as rp
from repro.launch.mesh import make_host_mesh
from repro.serve.stream import StreamConfig
from repro.serve.ts_engine import TSEngineConfig, TimeSurfaceEngine

H, W = 24, 32
cfg = TSEngineConfig(h=H, w=W, n_slots=4, chunk_capacity=64,
                     backend='interpret', block=(8, 16))
scfg = StreamConfig(policy='drop_oldest', queue_capacity=256,
                    deadline_s=0.01)

def feeds():
    return rp.mixed_scene_feeds(H, W, 0.05, 4, seed=12, churn=True)

plain = rp.replay(TimeSurfaceEngine(cfg), feeds(), scfg)
for nd in (2, 4):
    mesh = make_host_mesh(nd)
    rep = rp.replay(TimeSurfaceEngine(cfg, mesh=mesh), feeds(), scfg)
    assert rep.digests == plain.digests, f'{nd}-device digests diverged'
    assert (rep.ingested, rep.dropped, rep.discarded) == (
        plain.ingested, plain.dropped, plain.discarded), nd
    rp.check_oracle(rep, lambda: TimeSurfaceEngine(cfg, mesh=mesh))
    print(f'mesh {nd}: OK ({rep.n_steps} deadlines)')
"""


@pytest.mark.slow
def test_stream_mesh_multi_device_sweep():
    """Pipelined streaming over 2- and 4-device meshes: per-deadline
    digests, drop accounting, and the synchronous oracle all match the
    unsharded runtime bitwise (pool-shaped products pad to
    n_slots_padded == n_slots here, so digests compare directly)."""
    import os
    import subprocess
    import sys
    import textwrap

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    inherited = os.environ.get("PYTHONPATH")
    env = dict(os.environ, PYTHONPATH=(
        src + os.pathsep + inherited if inherited else src))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_MESH_SWEEP)],
        capture_output=True, text=True, env=env, timeout=1800,
    )
    assert out.returncode == 0, (
        f"mesh sweep failed\nSTDOUT:\n{out.stdout}\n"
        f"STDERR:\n{out.stderr[-3000:]}"
    )
    assert "mesh 2: OK" in out.stdout and "mesh 4: OK" in out.stdout

# ---------------------------------------------------------------------------
# QoS: per-sensor deadline streams, EDF, tiers, admission, flow control
# ---------------------------------------------------------------------------

def _tier_identity(row):
    return (row["ingested"] + row["dropped"] + row["refused"]
            + row["discarded"] + row["deferred"])


def test_qos_per_sensor_periods():
    """A sensor's deadline stream is its own: a 2x-period sensor is
    served on every other runtime deadline, the default-period one on
    every deadline."""
    rt = StreamRuntime(make_engine(), StreamConfig(deadline_s=0.01))
    fast = rt.connect()
    slow = rt.connect(stream.QoSClass(tier="slow", period_s=0.02))
    rng = np.random.default_rng(7)
    served = {fast.slot: 0, slow.slot: 0}
    for k in range(1, 5):
        fast.offer(events(rng, 8, t_lo=(k - 1) * 0.01, t_hi=k * 0.01))
        slow.offer(events(rng, 8, t_lo=(k - 1) * 0.01, t_hi=k * 0.01))
        rec = rt.step(k * 0.01)
        for slot, _tier, _d in rec.order:
            served[slot] += 1
    rt.flush()
    assert served[fast.slot] == 4
    # first step always serves (initial deadline -inf), then the sensor's
    # own stream takes over: deadlines at 0.02 and 0.04 only
    assert served[slow.slot] == 3
    assert slow.queued == 0             # each service drains the backlog


def test_qos_edf_order_determinism():
    """The recorded schedule is EDF (deadline, priority, slot) — ties
    break by priority then slot, and two identical runs record the
    identical order."""
    def run():
        rt = StreamRuntime(make_engine(), StreamConfig(deadline_s=0.01))
        lo = rt.connect(stream.QoSClass(tier="lo", priority=2))
        hi = rt.connect(stream.QoSClass(tier="hi", priority=0))
        mid = rt.connect(stream.QoSClass(tier="mid", priority=1))
        rng = np.random.default_rng(8)
        for cam in (lo, hi, mid):
            cam.offer(events(rng, 16, t_hi=0.01))
        rec = rt.step(0.01)
        rt.flush()
        return rec.order, (lo.slot, hi.slot, mid.slot)

    order, (lo_s, hi_s, mid_s) = run()
    # all deadlines equal (-inf at first step): priority decides
    assert [s for s, _, _ in order] == [hi_s, mid_s, lo_s]
    assert [t for _, t, _ in order] == ["hi", "mid", "lo"]
    order2, _ = run()
    assert order == order2

    # distinct deadlines dominate priority: after the first step a
    # short-period low-priority sensor is due before a long-period
    # high-priority one
    rt = StreamRuntime(make_engine(), StreamConfig(deadline_s=0.005))
    slow_hi = rt.connect(stream.QoSClass(tier="a", priority=0, period_s=0.02))
    fast_lo = rt.connect(stream.QoSClass(tier="b", priority=2, period_s=0.005))
    rng = np.random.default_rng(9)
    rt.step(0.005)                       # both served (deadline -inf)
    for cam in (slow_hi, fast_lo):
        cam.offer(events(rng, 8, t_hi=0.02))
    rec = rt.step(0.02)                  # both due: 0.01 (b) < 0.02 (a)... no:
    rt.flush()
    # fast_lo's next deadline after t=0.005 is 0.01, slow_hi's is 0.02 —
    # at t=0.02 both are due but EDF puts the EARLIER deadline first
    # despite its lower priority
    assert [s for s, _, _ in rec.order] == [fast_lo.slot, slow_hi.slot]


def test_qos_overload_priority_preempts_and_defers():
    """Under a step chunk budget, priority preempts EDF: gesture is
    served, telemetry deferred (deadline unmoved, counted, listed)."""
    rt = StreamRuntime(
        make_engine(),
        StreamConfig(deadline_s=0.01, queue_capacity=1 << 12,
                     step_chunk_budget=2),
    )
    tel = rt.connect(stream.TELEMETRY_TIER)
    ges = rt.connect(stream.GESTURE_TIER)
    rng = np.random.default_rng(10)
    tel.offer(events(rng, 2 * CAP, t_hi=0.01))   # needs 2 chunks
    ges.offer(events(rng, CAP, t_hi=0.01))       # needs 1 chunk
    rec = rt.step(0.01)
    rt.flush()
    assert rec.overload
    assert [t for _, t, _ in rec.order] == ["gesture"]
    assert rec.deferred == [(tel.slot, "telemetry", 2 * CAP)]
    assert tel.deferrals == 2 * CAP and tel.queued == 2 * CAP
    # telemetry's deadline did not advance: it leads the next EDF pass
    assert tel.next_deadline <= 0.01
    rec2 = rt.step(0.02)
    rt.flush()
    assert not rec2.overload
    assert tel.queued == 0 and tel.ingested == 2 * CAP
    tiers = rt.tier_counters()
    for row in tiers.values():
        assert row["offered"] == _tier_identity(row)


def test_qos_mixed_tier_overload_conservation():
    """Sustained 2x-overload with small telemetry queues: gesture is
    always served, telemetry absorbs the drops, and the per-tier
    conservation identity holds exactly at every step."""
    rt = StreamRuntime(
        make_engine(),
        StreamConfig(policy="drop_oldest", queue_capacity=CAP,
                     deadline_s=0.01, step_chunk_budget=2),
    )
    tels = [rt.connect(stream.TELEMETRY_TIER) for _ in range(2)]
    ges = rt.connect(stream.GESTURE_TIER)
    rng = np.random.default_rng(11)
    for k in range(1, 9):
        lo, hi = (k - 1) * 0.01, k * 0.01
        for tel in tels:
            tel.offer(events(rng, 2 * CAP, t_lo=lo, t_hi=hi))
        ges.offer(events(rng, CAP // 2, t_lo=lo, t_hi=hi))
        rec = rt.step(hi)
        assert any(t == "gesture" for _, t, _ in rec.order)
        tiers = rt.tier_counters()
        for tier, row in tiers.items():
            assert row["offered"] == _tier_identity(row), (k, tier, row)
    rt.flush()
    tiers = rt.tier_counters()
    assert tiers["gesture"]["dropped"] == 0
    assert tiers["gesture"]["ingested"] == 8 * (CAP // 2)
    assert tiers["telemetry"]["dropped"] > 0
    assert tiers["telemetry"]["deferrals"] > 0


def test_qos_admission_control():
    """connect() refuses a declared rate that exceeds the remaining
    capacity; freeing a sensor re-opens the budget."""
    rt = StreamRuntime(
        make_engine(), StreamConfig(capacity_eps=10_000.0))
    a = rt.connect(stream.QoSClass(tier="a", rate_hint=6_000.0))
    with pytest.raises(stream.AdmissionError) as ei:
        rt.connect(stream.QoSClass(tier="b", rate_hint=5_000.0))
    assert "10000" in str(ei.value).replace(",", "")
    b = rt.connect(stream.QoSClass(tier="b", rate_hint=4_000.0))
    rt.disconnect(a)
    c = rt.connect(stream.QoSClass(tier="c", rate_hint=6_000.0))
    assert {s.qos.tier for s in rt.sensors.values()} == {"b", "c"}
    assert b.slot != c.slot


def test_qos_admission_uses_observed_drain_rate():
    """An under-declared producer still counts: admission demand is
    max(declared, observed EWMA), so a sensor that declared 0 but
    drains 32 events / 10ms blocks a declared rate that would fit on
    paper."""
    rt = StreamRuntime(
        make_engine(),
        StreamConfig(deadline_s=0.01, capacity_eps=4_000.0),
    )
    liar = rt.connect(stream.QoSClass(tier="liar", rate_hint=0.0))
    rng = np.random.default_rng(12)
    for k in range(1, 4):
        liar.offer(events(rng, 32, t_lo=(k - 1) * 0.01, t_hi=k * 0.01))
        rt.step(k * 0.01)
    rt.flush()
    assert liar.drain_eps is not None and liar.drain_eps > 3_000.0
    with pytest.raises(stream.AdmissionError):
        rt.connect(stream.QoSClass(tier="b", rate_hint=1_000.0))


def test_offer_retry_after_flow_control():
    """OfferResult is an int (exact consumed count, back-compat) with a
    retry_after hint: 0 while there is room, positive and derived from
    the observed drain rate once the queue overflows."""
    rt = StreamRuntime(
        make_engine(),
        StreamConfig(policy="block", queue_capacity=CAP, deadline_s=0.01),
    )
    cam = rt.connect()
    rng = np.random.default_rng(13)
    r = cam.offer(events(rng, CAP // 2, t_hi=0.01))
    assert r == CAP // 2 and isinstance(r, int)
    assert r.accepted == CAP // 2 and r.retry_after == 0.0
    # no drain observed yet: the hint falls back to the sensor period
    r = cam.offer(events(rng, CAP, t_hi=0.01))
    assert r == CAP // 2 and r.refused == CAP // 2
    assert r.retry_after == pytest.approx(0.01)
    rt.step(0.01)
    rt.flush()
    assert cam.drain_eps == pytest.approx(CAP / 0.01)
    # drain observed: the hint is backlog / drain rate
    r = cam.offer(events(rng, CAP + 10, t_lo=0.01, t_hi=0.02))
    assert r == CAP and r.refused == 10
    assert r.retry_after == pytest.approx(10 / cam.drain_eps)


def test_set_tier_migrates_queued_attribution():
    """Tier migration moves the queued (unserved) events' attribution
    to the new tier; served/dropped history stays with the old one."""
    rt = StreamRuntime(
        make_engine(),
        StreamConfig(policy="drop_oldest", queue_capacity=CAP,
                     deadline_s=0.01),
    )
    cam = rt.connect(stream.TELEMETRY_TIER)
    rng = np.random.default_rng(14)
    cam.offer(events(rng, CAP + 16, t_hi=0.01))      # 16 evicted
    rt.step(0.01)                                     # CAP ingested
    rt.flush()
    cam.offer(events(rng, 24, t_lo=0.01, t_hi=0.02))  # queued at migration
    rt.set_tier(cam, stream.GESTURE_TIER)
    tiers = rt.tier_counters()
    assert tiers["telemetry"]["ingested"] == CAP
    assert tiers["telemetry"]["dropped"] == 16
    assert tiers["telemetry"]["deferred"] == 0
    assert tiers["gesture"]["offered"] == 24 == tiers["gesture"]["deferred"]
    for row in tiers.values():
        assert row["offered"] == _tier_identity(row)
    rt.step(0.02)
    rt.flush()
    tiers = rt.tier_counters()
    assert tiers["gesture"]["ingested"] == 24
    for row in tiers.values():
        assert row["offered"] == _tier_identity(row)
    # the log records the migration for the oracle
    kinds = [k for k, _ in rt.log]
    assert kinds.count("set_tier") == 1


def test_qos_churn_migration_replay_oracle():
    """The full QoS gauntlet replays bitwise through the synchronous
    oracle: tiered feeds, churn, mid-run tier migration, overload
    budget — pipelining/EDF/preemption may move when work happens,
    never what it computes."""
    feeds = rp.mixed_scene_feeds(H, W, 0.06, 6, seed=2, churn=True,
                                 tiered=True)
    assert any(f.migrate is not None for f in feeds)
    assert {f.qos.tier for f in feeds} == {"gesture", "telemetry"}
    cfg = make_cfg(n_slots=6)
    report = rp.replay(
        TimeSurfaceEngine(cfg), feeds,
        StreamConfig(policy="drop_oldest", queue_capacity=256,
                     deadline_s=0.01, step_chunk_budget=3),
    )
    n = rp.check_oracle(report, lambda: TimeSurfaceEngine(cfg))
    assert n == report.n_steps > 0
    kinds = [k for k, _ in report.log]
    assert kinds.count("set_tier") >= 1
    for tier, row in report.tiers.items():
        assert row["offered"] == _tier_identity(row), (tier, row)
    # determinism: the same feeds replay to the same digests
    report2 = rp.replay(
        TimeSurfaceEngine(cfg),
        rp.mixed_scene_feeds(H, W, 0.06, 6, seed=2, churn=True,
                             tiered=True),
        StreamConfig(policy="drop_oldest", queue_capacity=256,
                     deadline_s=0.01, step_chunk_budget=3),
    )
    assert report.digests == report2.digests


# ---------------------------------------------------------------------------
# long-horizon timestamp precision (epoch rebasing)
# ---------------------------------------------------------------------------

def test_long_horizon_timestamps_bitwise():
    """Regression: a session starting at t0 = 3600 s reads out bit for
    bit what the same events read at t0 = 0.  Offsets are multiples of
    1/8192 s — exact in float64 at any t0 and exact in float32 near
    zero, but NOT representable in float32 at 3600 s (ulp there is
    1/4096 s) — so the pre-epoch code, which cast absolute stamps to
    float32 on offer, quantized them and diverged."""
    rng = np.random.default_rng(20)
    n = 96
    offs = np.sort(rng.integers(1, 800, n)) / 8192.0          # float64
    xs = rng.integers(0, W, n).astype(np.int32)
    ys = rng.integers(0, H, n).astype(np.int32)
    ps = rng.integers(0, 2, n).astype(np.int32)

    # the premise: some absolute stamps at 3600 s are not float32-exact
    abs_t = 3600.0 + offs
    assert (np.float64(np.float32(abs_t)) != abs_t).any()

    def run(t0):
        rt = StreamRuntime(make_engine(), StreamConfig())
        cam = rt.connect()
        cam.offer((xs, ys, t0 + offs, ps))
        rec = rt.step(t0 + 0.125)                 # dyadic: exact either way
        out = np.asarray(rt.flush()["surface"])
        return out, rec.digest, rt.t_epoch

    base, d0, e0 = run(0.0)
    far, d1, e1 = run(3600.0)
    assert e0 == 0.0 and e1 == 3600.0             # whole-second floor
    np.testing.assert_array_equal(far, base)
    assert d0 == d1 and d0


def test_epoch_floor_keeps_subsecond_sessions_at_zero():
    """A session whose first stamp is inside its first second pins epoch
    0 — engine-facing times are bitwise the pre-epoch absolute times."""
    rt = StreamRuntime(make_engine(), StreamConfig())
    cam = rt.connect()
    ev = events(np.random.default_rng(21), 40)
    assert ev.t[0] > 0                            # strictly inside (0, 1)
    cam.offer(ev)
    rec = rt.step(0.06)
    rt.flush()
    assert rt.t_epoch == 0.0 and rec.t_read == 0.06
    assert rt.stats()["t_epoch"] == 0.0
    # the log carries the (here: identical) rebased stamps the oracle eats
    _, (_, _, lt, _) = rec.chunks[0]
    np.testing.assert_array_equal(lt, ev.t)


def test_long_horizon_replay_oracle():
    """The action log records rebased times, so the replay oracle gates
    a 3600-s-old session without knowing about epochs."""
    rng = np.random.default_rng(22)
    n = 200
    offs = np.sort(rng.integers(1, 300, n)) / 8192.0
    stream_far = syn.EventStream(
        x=rng.integers(0, W, n).astype(np.int32),
        y=rng.integers(0, H, n).astype(np.int32),
        t=(3600.0 + offs).astype(np.float64),
        p=rng.integers(0, 2, n).astype(np.int32),
        is_signal=np.ones(n, bool), h=H, w=W,
    )
    cfg = make_cfg()
    rt = StreamRuntime(TimeSurfaceEngine(cfg),
                       StreamConfig(deadline_s=0.01))
    cam = rt.connect()
    cam.offer((stream_far.x, stream_far.y, stream_far.t, stream_far.p))
    for k in range(1, 5):
        rt.step(3600.0 + k * 0.01 + 0.0625)
    rt.flush()
    digests = [e.digest for kind, e in rt.log if kind == "step"]
    # rebuild from the log exactly like events.replay's oracle does:
    # fresh engine, recorded chunks, recorded (rebased) read times
    oracle = TimeSurfaceEngine(cfg)
    cam2 = oracle.attach()
    for kind, e in rt.log:
        if kind != "step":
            continue
        for slot, (x, y, t, p) in e.chunks:
            assert slot == cam.slot
            cam2.push(syn.EventStream(
                x=x, y=y, t=t, p=p, is_signal=np.ones(len(x), bool),
                h=H, w=W))
        got = oracle.read(rt.spec, e.t_read)
        assert stream.digest_products(got) == digests.pop(0)


# ---------------------------------------------------------------------------
# replay digest: a SHA-256 tree of 1 MiB leaves
# ---------------------------------------------------------------------------

LEAF = stream.DIGEST_LEAF_BYTES


def tree_digest(products):
    """The digest's layout, spelled out: per array in name order the
    header, then the raw digest of each leaf of its C-order bytes."""
    h = hashlib.sha256()
    for name in sorted(products):
        a = np.asarray(products[name])
        h.update(name.encode())
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        raw = a.tobytes()
        for i in range(0, max(len(raw), 1), LEAF):
            h.update(hashlib.sha256(raw[i:i + LEAF]).digest())
    return h.hexdigest()


def pool_products(seed=0):
    """Pool-shaped products over more than one leaf: surface and count
    two leaves each (the second partial), logits under one."""
    rng = np.random.default_rng(seed)
    return {"surface": rng.random((2, 2, 240, 320), dtype=np.float32),
            "count": rng.integers(0, 16, (2, 2, 240, 320)).astype(np.int32),
            "logits": rng.standard_normal((2, 10)).astype(np.float32)}


def minor_dims_swapped(a):
    """``a`` laid out as a TPU's host copy of a pool-shaped product is:
    the same values, the two minor dims swapped in memory."""
    return np.ascontiguousarray(a.swapaxes(-1, -2)).swapaxes(-1, -2)


@pytest.mark.parametrize("workers", [1, 2, 16])
def test_digest_is_the_leaf_tree_whatever_the_workers(monkeypatch, workers):
    from concurrent.futures import ThreadPoolExecutor

    prods = pool_products()
    prods["stcf"] = minor_dims_swapped(
        np.random.default_rng(1).random((4, 2, 240, 320), dtype=np.float32))
    assert not prods["stcf"].flags.c_contiguous
    assert prods["surface"].nbytes > LEAF > prods["logits"].nbytes
    with ThreadPoolExecutor(max_workers=workers) as pool:
        monkeypatch.setattr(stream, "_DIGEST_POOL", pool)
        got, leaves, used = stream._digest([prods])
        assert got == stream.digest_products(prods)
    assert got == tree_digest(prods)
    assert leaves == 2 + 2 + 1 + 3
    assert 1 <= used <= workers


@pytest.mark.parametrize("products", [
    {"surface": np.zeros((4, 24, 32), np.float32)},
    {"empty": np.zeros((0, 3), np.float32), "scalar": np.float32(2.5)},
    {"mask": np.ones((LEAF + 1,), bool), "logits": np.arange(6.0)},
], ids=["one-small-leaf", "empty-and-scalar", "bool-over-a-leaf"])
def test_digest_layout_edge_shapes(products):
    assert stream.digest_products(products) == tree_digest(products)
    assert stream._digest([products])[1] == sum(
        max(1, -(-np.asarray(a).nbytes // LEAF)) for a in products.values())


@pytest.mark.parametrize("name,byte", [
    ("surface", LEAF - 1),          # last byte of the first leaf
    ("surface", LEAF),              # first byte of the second
    ("surface", -1),                # last byte of the last leaf
    ("count", LEAF),
    ("logits", 0),                  # an array under one leaf
    ("logits", -1),
])
def test_digest_sees_one_flipped_byte(name, byte):
    prods = pool_products()
    base = stream.digest_products(prods)
    prods[name].reshape(-1).view(np.uint8)[byte] ^= 1
    assert stream.digest_products(prods) != base


@pytest.mark.parametrize("change", ["shape", "dtype", "name"])
def test_digest_sees_the_header(change):
    prods = pool_products()
    base = stream.digest_products(prods)
    a = prods["surface"]
    if change == "shape":
        prods["surface"] = a.reshape(4, 240, 320)
    elif change == "dtype":
        prods["surface"] = a.view(np.int32)
    else:
        prods["surfaces"] = prods.pop("surface")
    assert stream.digest_products(prods) != base


@pytest.mark.parametrize("layout", ["strided", "transposed", "fortran",
                                    "minor-dims-swapped", "read-only",
                                    "device"])
def test_digest_of_a_view_is_that_of_its_contiguous_copy(layout):
    import jax.numpy as jnp

    base = np.random.default_rng(1).random((4, 2, 240, 320),
                                           dtype=np.float32)
    a = {"strided": lambda: base[::2],
         "transposed": lambda: base.transpose(0, 1, 3, 2),
         "fortran": lambda: np.asfortranarray(base),
         "minor-dims-swapped": lambda: minor_dims_swapped(base),
         "read-only": lambda: np.asarray(jnp.asarray(base)),
         "device": lambda: jnp.asarray(base)}[layout]()
    host = np.asarray(a)
    if layout != "device":
        assert not (host.flags.c_contiguous and host.flags.writeable)
    want = stream.digest_products({"surface": np.ascontiguousarray(host)})
    assert stream.digest_products({"surface": a}) == want


def test_digest_of_an_array_two_specs_share():
    """Its leaves hash once, and the step digests as with two copies."""
    p = pool_products()
    stage0 = {"surface": p["surface"], "logits": p["logits"]}
    shared = [stage0, {"surface": p["surface"], "count": p["count"]}]
    copied = [stage0, {"surface": p["surface"].copy(), "count": p["count"]}]
    got, leaves, _ = stream._digest(shared)
    want, leaves_copied, _ = stream._digest(copied)
    assert got == want == stream.digest_step(copied)
    assert leaves == leaves_copied - 2 == 5
    h = hashlib.sha256()
    for prods in copied:
        h.update(tree_digest(prods).encode())
    assert got == h.hexdigest()


# ---------------------------------------------------------------------------
# device-resident ingest ring
# ---------------------------------------------------------------------------

def test_device_ring_bitwise_vs_host_staged():
    """The ring path (device_ring=True, the default) and the host-staged
    comparator produce identical per-deadline digests over mixed
    traffic, and the ring run passes the synchronous replay oracle."""
    cfg = make_cfg()

    def run(device_ring):
        return rp.replay(
            TimeSurfaceEngine(cfg),
            rp.mixed_scene_feeds(H, W, 0.05, 4, seed=30),
            StreamConfig(policy="drop_oldest", queue_capacity=256,
                         deadline_s=0.01, device_ring=device_ring),
        )

    ring, host = run(True), run(False)
    assert ring.digests == host.digests
    assert (ring.ingested, ring.dropped) == (host.ingested, host.dropped)
    n = rp.check_oracle(ring, lambda: TimeSurfaceEngine(cfg))
    assert n == ring.n_steps > 0


def test_device_ring_mesh_single_device_bitwise():
    """Same gate over a 1-device mesh: the shard-major staging path
    (``_stage_sharded`` + pre-sharded upload) matches both the unsharded
    ring and the host-staged mesh run."""
    import dataclasses

    from repro.launch.mesh import make_host_mesh

    cfg = make_cfg()
    scfg = StreamConfig(policy="drop_oldest", queue_capacity=256,
                        deadline_s=0.01)

    def run(mesh, device_ring):
        return rp.replay(
            TimeSurfaceEngine(cfg, mesh=mesh),
            rp.mixed_scene_feeds(H, W, 0.05, 4, seed=31),
            dataclasses.replace(scfg, device_ring=device_ring),
        )

    plain = run(None, True)
    mesh_ring = run(make_host_mesh(1), True)
    mesh_host = run(make_host_mesh(1), False)
    assert mesh_ring.digests == plain.digests == mesh_host.digests
    rp.check_oracle(mesh_ring,
                    lambda: TimeSurfaceEngine(cfg, mesh=make_host_mesh(1)))


def test_push_staged_equals_push():
    """Direct engine-level gate: ``push_staged`` raw parts vs ``push``
    of the same events give the same surface bits, including partial
    chunks and multiple sensors per dispatch."""
    rng = np.random.default_rng(32)
    eng_a, eng_b = make_engine(), make_engine()
    cams_a = [eng_a.attach() for _ in range(2)]
    cams_b = [eng_b.attach() for _ in range(2)]
    evs = [events(rng, CAP + 17), events(rng, 23)]
    eng_a.push(list(zip(cams_a, evs)))
    items = []
    for cam, ev in zip(cams_b, evs):
        for lo in range(0, ev.n, CAP):
            part = tuple(a[lo:lo + CAP] for a in (ev.x, ev.y, ev.t, ev.p))
            items.append((cam.slot, part))
    eng_b.push_staged(items)
    for t_read in (0.06, 0.08):
        a = eng_a.read(rs.SURFACE_SPEC, t_read)
        b = eng_b.read(rs.SURFACE_SPEC, t_read)
        np.testing.assert_array_equal(np.asarray(b["surface"]),
                                      np.asarray(a["surface"]))


def test_push_staged_validates_parts():
    eng = make_engine()
    cam = eng.attach()
    ev = events(np.random.default_rng(33), CAP + 1)
    part = (ev.x, ev.y, ev.t, ev.p)
    with pytest.raises(AssertionError, match="chunk capacity"):
        eng.push_staged([(cam.slot, part)])
    with pytest.raises(ValueError, match="not acquired"):
        eng.push_staged([(3, tuple(a[:4] for a in part))])
    eng.push_staged([])                           # explicit no-op


def test_ingest_ring_rotation_and_zero_fill():
    """The ring alternates staging sets per padded batch size and
    re-zeroes on acquire, so a stale row from two steps ago can never
    leak into a later, smaller dispatch."""
    from repro.serve.ts_engine import IngestRing

    ring = IngestRing(capacity=8, depth=2)
    a = ring.acquire(2)
    IngestRing.fill_row(a, 1, 3, (np.array([5], np.int32),) * 4)
    b = ring.acquire(2)
    assert b is not a                             # double buffered
    assert ring.acquire(2) is a                   # rotation wraps
    assert a["sids"][1] == 0 and not a["valid"].any()   # re-zeroed
    # distinct padded sizes keep distinct sets
    c = ring.acquire(4)
    assert c["x"].shape == (4, 8) and a["x"].shape == (2, 8)


def test_stream_runtime_ring_off_matches_on():
    """StreamRuntime honors device_ring=False (host-staged comparator)
    and both modes drain/account identically."""
    def run(device_ring):
        rt = StreamRuntime(
            make_engine(),
            StreamConfig(queue_capacity=1 << 12, device_ring=device_ring))
        cam = rt.connect()
        cam.offer(events(np.random.default_rng(34), 2 * CAP + 9))
        rec = rt.step(0.06)
        out = np.asarray(rt.flush()["surface"])
        return out, rec.digest, cam.ingested

    on, off = run(True), run(False)
    np.testing.assert_array_equal(on[0], off[0])
    assert on[1] == off[1] and on[2] == off[2] == 2 * CAP + 9


# ---------------------------------------------------------------------------
# flow-control edges
# ---------------------------------------------------------------------------

def test_retry_after_before_any_drain_falls_back_to_period():
    """drain_eps unset (no deadline has drained yet) vs observed: the
    hint falls back to the sensor's own period, not the runtime's."""
    rt = StreamRuntime(
        make_engine(),
        StreamConfig(policy="block", queue_capacity=8, deadline_s=0.01))
    cam = rt.connect(stream.QoSClass(tier="slow", period_s=0.04))
    assert cam.drain_eps is None
    r = cam.offer(events(np.random.default_rng(40), 12))
    assert r == 8 and r.refused == 4
    assert r.retry_after == pytest.approx(0.04)   # period, drain unknown


def test_idle_deadlines_do_not_fabricate_drain_rate():
    """Steps that drain nothing leave the EWMA unset — an idle sensor
    must not observe a zero rate (which would blow the hint up)."""
    rt = StreamRuntime(make_engine(), StreamConfig(deadline_s=0.01))
    cam = rt.connect()
    for k in range(1, 4):
        rt.step(k * 0.01)                         # served, zero drained
    rt.flush()
    assert cam.drain_eps is None
    assert cam.offer((np.array([], np.int32),) * 4).retry_after == 0.0


def test_offer_empty_and_result_semantics():
    """OfferResult int/truthiness: a short block-policy offer is falsy
    exactly when nothing was consumed; drop_newest consumes (truthily)
    even when everything drops."""
    rt = StreamRuntime(
        make_engine(), StreamConfig(policy="block", queue_capacity=4))
    cam = rt.connect()
    empty = (np.array([], np.int32),) * 4
    r = cam.offer(empty)
    assert r == 0 and not r and r.retry_after == 0.0
    ev = events(np.random.default_rng(41), 4)
    full = cam.offer(ev)
    assert full and full == 4 and full + 1 == 5   # plain int arithmetic
    again = cam.offer(ev)
    assert not again and again.refused == 4       # blocked: falsy
    assert again.retry_after > 0.0

    rt2 = StreamRuntime(
        make_engine(), StreamConfig(policy="drop_newest", queue_capacity=4))
    cam2 = rt2.connect()
    cam2.offer(ev)
    r2 = cam2.offer(ev)                           # queue full: all dropped
    assert r2 == 4 and bool(r2)                   # consumed, hence truthy
    assert r2.accepted == 0 and r2.dropped == 4
    assert cam2.offer(empty) == 0


def test_ewma_spans_deferred_steps():
    """A sensor deferred by overload keeps its EWMA window open: when it
    finally drains, the instantaneous rate is measured over the full
    interval since its last service, not one period — so deferral slows
    the observed rate instead of hiding it."""
    rt = StreamRuntime(
        make_engine(),
        StreamConfig(deadline_s=0.01, queue_capacity=1 << 12,
                     step_chunk_budget=1))
    tel = rt.connect(stream.TELEMETRY_TIER)
    ges = rt.connect(stream.GESTURE_TIER)
    rng = np.random.default_rng(42)
    rt.step(0.01)                                 # both served empty
    assert tel.drain_eps is None
    tel.offer(events(rng, CAP, t_lo=0.01, t_hi=0.02))
    ges.offer(events(rng, CAP, t_lo=0.01, t_hi=0.02))
    rec = rt.step(0.02)                           # budget 1: tel defers
    assert rec.overload and tel.deferrals == CAP
    assert tel.drain_eps is None                  # no drain, no update
    rt.step(0.03)                                 # tel finally drains
    rt.flush()
    # CAP events over the 0.01 -> 0.03 window, not over one period
    assert tel.drain_eps == pytest.approx(CAP / 0.02)
    tel.offer(events(rng, CAP // 2, t_lo=0.03, t_hi=0.04))
    rt.step(0.04)
    rt.flush()
    inst = (CAP // 2) / 0.01
    want = 0.3 * inst + 0.7 * (CAP / 0.02)        # the EWMA folds in
    assert tel.drain_eps == pytest.approx(want)


def test_qos_multi_spec_step_reads():
    """Sensors carrying their own ReadoutSpec get it served in the same
    step (one fused dispatch per unique spec), bit-identical to plain
    reads, and the oracle digests cover every spec."""
    count_spec = rs.ReadoutSpec(surface=rs.surface(), count=rs.count(4))
    cfg = TSEngineConfig(h=H, w=W, n_slots=4, chunk_capacity=CAP,
                         backend="interpret", block=(8, 16),
                         specs=(count_spec,))
    rt = StreamRuntime(TimeSurfaceEngine(cfg), StreamConfig(deadline_s=0.01))
    plain = rt.connect()
    counted = rt.connect(stream.QoSClass(tier="counted", spec=count_spec))
    rng = np.random.default_rng(15)
    for cam in (plain, counted):
        cam.offer(events(rng, 32, t_hi=0.01))
    rec = rt.step(0.01)
    rt.flush()
    assert rec.specs == (rt.spec, count_spec)
    want = rt.engine.read(count_spec, 0.01)
    got = rt.engine.read_many((rt.spec, count_spec, count_spec), 0.01)
    assert len(got) == 2                      # deduped
    for name in want:
        assert (np.asarray(got[count_spec][name])
                == np.asarray(want[name])).all()


# ---------------------------------------------------------------------------
# fleet elasticity + live migration
# ---------------------------------------------------------------------------

def make_elastic_cfg(bucket=2, **kw):
    return TSEngineConfig(h=H, w=W, n_slots=bucket, slot_bucket=bucket,
                          chunk_capacity=CAP, backend="interpret",
                          block=(8, 16), **kw)


def test_elastic_grow_at_exact_bucket_boundary():
    """connect() grows exactly when the next admission would cross the
    watermark — at the bucket boundary, not one early — and the live
    surface bits survive the copy into the wider pool."""
    rt = StreamRuntime(TimeSurfaceEngine(make_elastic_cfg(bucket=2)),
                       StreamConfig(elastic=True, deadline_s=0.01))
    eng = rt.engine
    a = rt.connect()
    rt.connect()                         # pool exactly full: no grow yet
    assert eng.capacity == 2
    assert [k for k, _ in rt.log if k == "grow"] == []
    ev = events(np.random.default_rng(60), 30)
    a.offer(ev)
    rt.step(0.06)
    rt.flush()
    before = np.asarray(
        eng.read(rs.SURFACE_SPEC, 0.06)["surface"])[a.slot].copy()
    c = rt.connect()                     # boundary crossed: one bucket
    assert eng.capacity == 4 and c.slot == 2
    assert [e for k, e in rt.log if k == "grow"] == [4]
    after = np.asarray(eng.read(rs.SURFACE_SPEC, 0.06)["surface"])[a.slot]
    np.testing.assert_array_equal(after, before)

    # max_slots caps growth: a full capped pool refuses, never grows
    rt2 = StreamRuntime(TimeSurfaceEngine(make_elastic_cfg(bucket=2)),
                        StreamConfig(elastic=True, max_slots=4))
    for _ in range(4):
        rt2.connect()
    assert rt2.engine.capacity == 4
    with pytest.raises(RuntimeError):
        rt2.connect()
    assert rt2.engine.capacity == 4


def test_elastic_shrink_compacts_head_bearing_tail():
    """The shrink watermark releases a bucket with a head-bearing tier
    sensor resident in the released tail: its slot compacts downward
    and the surface AND the stage-1 head products keep their bits."""
    import dataclasses

    head_spec = rs.ReadoutSpec(surface=rs.surface(),
                               logits=rs.classify(n_classes=4, width=8))
    rt = StreamRuntime(
        TimeSurfaceEngine(make_elastic_cfg(bucket=2)),
        StreamConfig(policy="drop_oldest", queue_capacity=256,
                     deadline_s=0.01, elastic=True, shrink_watermark=0.9))
    a, b = rt.connect(), rt.connect()
    ges = rt.connect(dataclasses.replace(stream.GESTURE_TIER,
                                         spec=head_spec))
    assert rt.engine.capacity == 4 and ges.slot == 2    # in the tail
    ges.offer(events(np.random.default_rng(61), 50, t_hi=0.01))
    rt.step(0.01)
    rt.flush()
    out = rt.engine.read(head_spec, 0.01)
    surf_before = np.asarray(out["surface"])[ges.slot].copy()
    logits_before = np.asarray(out["logits"])[ges.slot].copy()
    rt.disconnect(a)
    rt.disconnect(b)
    rt.step(0.02)                        # occupancy 1 <= 0.9 * 2: shrink
    rt.flush()
    assert [e for k, e in rt.log if k == "shrink"] == [(2, [(2, 0)])]
    assert rt.engine.capacity == 2
    assert ges.slot == 0 and rt.sensors[0] is ges
    out2 = rt.engine.read(head_spec, 0.01)
    np.testing.assert_array_equal(np.asarray(out2["surface"])[0],
                                  surf_before)
    np.testing.assert_array_equal(np.asarray(out2["logits"])[0],
                                  logits_before)


def test_migrate_preserves_deferred_deadline_and_analog_noise():
    """migrate() moves a sensor with a deferred deadline (queue intact,
    deadline unmoved, queued events counted in ``migrated``) and a slot
    whose analog noise generation is non-zero — the generation value
    travels with the state, so the per-cell noise draw at the
    destination is bitwise the source's."""
    import dataclasses

    from repro.serve import fidelity as fm

    analog_spec = rs.ReadoutSpec(
        surface=rs.surface(fidelity=fm.analog_3d()))
    cfg = TSEngineConfig(h=H, w=W, n_slots=4, slot_bucket=2,
                         chunk_capacity=CAP, mode="edram",
                         backend="interpret", block=(8, 16))
    rt = StreamRuntime(
        TimeSurfaceEngine(cfg),
        StreamConfig(policy="drop_oldest", queue_capacity=1 << 12,
                     deadline_s=0.01, step_chunk_budget=1, elastic=True))
    tmp = rt.connect()                   # bump slot 0's generation
    rt.disconnect(tmp)
    ges = rt.connect(dataclasses.replace(stream.GESTURE_TIER,
                                         spec=analog_spec))
    tel = rt.connect(stream.TELEMETRY_TIER)
    rng = np.random.default_rng(62)
    ges.offer(events(rng, CAP, t_hi=0.01))
    tel.offer(events(rng, CAP, t_hi=0.01))
    rec = rt.step(0.01)                  # budget 1: telemetry defers
    rt.flush()
    assert rec.overload and tel.deferrals == CAP and tel.queued == CAP
    assert tel.next_deadline <= 0.01     # deadline unmoved by deferral
    gen_before = int(np.asarray(rt.engine.state.generation)[ges.slot])
    assert gen_before > 1                # reused slot: non-initial gen
    noise_before = np.asarray(
        rt.engine.read(analog_spec, 0.01)["surface"])[ges.slot].copy()

    src_g, src_t = ges.slot, tel.slot
    dst_g = rt.migrate(ges)
    dst_t = rt.migrate(tel)
    assert dst_g != src_g and dst_t != src_t
    assert ges.slot == dst_g and rt.sensors[dst_g] is ges
    assert tel.queued == CAP and tel.next_deadline <= 0.01
    assert tel.migrated == CAP and ges.migrated == 0    # empty queue
    assert int(np.asarray(rt.engine.state.generation)[dst_g]) == gen_before
    noise_after = np.asarray(
        rt.engine.read(analog_spec, 0.01)["surface"])[dst_g]
    np.testing.assert_array_equal(noise_after, noise_before)

    rt.step(0.02)                        # deferred queue drains at dst
    rt.flush()
    assert tel.queued == 0 and tel.ingested == CAP
    assert [k for k, _ in rt.log].count("migrate") == 2
    tiers = rt.tier_counters()
    for tier, row in tiers.items():
        assert row["offered"] == _tier_identity(row), (tier, row)
    assert tiers["telemetry"]["migrated"] == CAP


def test_migrate_then_set_tier_ordering():
    """A set_tier immediately after migrate() logs in order, names the
    sensor's *new* slot, and the queued attribution moves tiers while
    the ``migrated`` count stays with the tier that owned the queue."""
    rt = StreamRuntime(
        TimeSurfaceEngine(make_elastic_cfg(bucket=4)),
        StreamConfig(policy="drop_oldest", queue_capacity=256,
                     deadline_s=0.01, elastic=True))
    cam = rt.connect(stream.TELEMETRY_TIER)
    cam.offer(events(np.random.default_rng(63), 24, t_hi=0.01))
    src = cam.slot
    dst = rt.migrate(cam)
    rt.set_tier(cam, stream.GESTURE_TIER)
    tail = [(k, e) for k, e in rt.log if k in ("migrate", "set_tier")]
    assert tail[0] == ("migrate", (src, dst))
    assert tail[1][0] == "set_tier" and tail[1][1][0] == dst
    tiers = rt.tier_counters()
    assert tiers["telemetry"]["migrated"] == 24
    assert tiers["gesture"]["offered"] == 24
    assert tiers["telemetry"]["offered"] == 0
    for tier, row in tiers.items():
        assert row["offered"] == _tier_identity(row), (tier, row)
    rt.step(0.01)
    rt.flush()
    tiers = rt.tier_counters()
    assert tiers["gesture"]["ingested"] == 24
    for tier, row in tiers.items():
        assert row["offered"] == _tier_identity(row), (tier, row)


def test_shard_budget_and_barrier_single_shard():
    """``shard_budget`` on a single-device engine caps the one shard:
    telemetry defers behind gesture on regular steps, and every Nth
    deadline is a barrier — budgets lift, everyone drains, and the
    per-shard virtual clock re-syncs to the deadline."""
    rt = StreamRuntime(
        make_engine(),
        StreamConfig(deadline_s=0.01, queue_capacity=1 << 12,
                     shard_budget=1, shard_barrier_every=3))
    tel = rt.connect(stream.TELEMETRY_TIER)
    ges = rt.connect(stream.GESTURE_TIER)
    rng = np.random.default_rng(64)
    recs = []
    for k in range(1, 7):
        lo, hi = (k - 1) * 0.01, k * 0.01
        tel.offer(events(rng, CAP, t_lo=lo, t_hi=hi))
        ges.offer(events(rng, CAP, t_lo=lo, t_hi=hi))
        recs.append(rt.step(hi))
    rt.flush()
    assert [r.barrier for r in recs] == [False, False, True] * 2
    for r in recs:
        served = {t for _, t, _ in r.order}
        if r.barrier:
            assert served == {"gesture", "telemetry"}   # budget lifted
        else:
            assert served == {"gesture"} and r.overload
    assert tel.queued == 0                # barriers drained the backlog
    assert rt.stats()["shard_clocks"][0] == pytest.approx(0.06)
    tiers = rt.tier_counters()
    for tier, row in tiers.items():
        assert row["offered"] == _tier_identity(row), (tier, row)


def test_fleet_churn_elastic_migration_replay_oracle():
    """The fleet acceptance gate, single-device: attach waves grow the
    pool >= 2x, three sensors live-migrate mid-run (one on the analog,
    head-bearing gesture tier), late detaches trigger one compacting
    shrink — and the whole schedule (grows, moves, migrations riding
    the action log) replays bitwise through the synchronous oracle with
    exact per-tier conservation and migrated-event attribution."""
    cfg = TSEngineConfig(h=H, w=W, n_slots=3, slot_bucket=3,
                         chunk_capacity=1 << 10, mode="edram",
                         backend="interpret", block=(8, 16))
    scfg = StreamConfig(policy="drop_oldest", deadline_s=0.005,
                        elastic=True, shrink_watermark=0.9,
                        step_chunk_budget=6, pipeline=True)
    feeds = rp.fleet_scene_feeds(H, W, 0.06, 9, seed=3, noise_hz=20.0)
    report = rp.replay(TimeSurfaceEngine(cfg), feeds, scfg,
                       arrival_substeps=2)
    n = rp.check_oracle(report, lambda: TimeSurfaceEngine(cfg))
    assert n == report.n_steps > 0
    grows = [e for k, e in report.log if k == "grow"]
    shrinks = [e for k, e in report.log if k == "shrink"]
    migs = [e for k, e in report.log if k == "migrate"]
    assert len(grows) >= 2, grows
    assert len(shrinks) == 1, shrinks
    assert len(migs) == 3, migs
    assert report.migrated > 0
    for tier, row in report.tiers.items():
        assert row["offered"] == _tier_identity(row), (tier, row)
    assert sum(r["migrated"] for r in report.tiers.values()) \
        == report.migrated
    assert report.tiers["gesture"]["migrated"] > 0   # the analog mover


# the fleet mesh sweep runs in a subprocess so the main test process
# stays single-device (same pattern as test_stream_mesh_multi_device_sweep)
_FLEET_MESH_SWEEP = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import numpy as np
from repro.events import replay as rp
from repro.launch.mesh import make_host_mesh
from repro.serve.stream import StreamConfig
from repro.serve.ts_engine import TSEngineConfig, TimeSurfaceEngine

H, W = 24, 32
cfg = TSEngineConfig(h=H, w=W, n_slots=3, slot_bucket=3,
                     chunk_capacity=1 << 10, mode='edram',
                     backend='interpret', block=(8, 16))

def scfg(**kw):
    return StreamConfig(policy='drop_oldest', deadline_s=0.005,
                        elastic=True, shrink_watermark=0.9,
                        step_chunk_budget=6, pipeline=True, **kw)

def feeds():
    return rp.fleet_scene_feeds(H, W, 0.06, 9, seed=3, noise_hz=20.0)

def identity(row):
    return (row['ingested'] + row['dropped'] + row['refused']
            + row['discarded'] + row['deferred'])

for nd in (1, 2):
    mesh = make_host_mesh(nd)
    mk = lambda: TimeSurfaceEngine(cfg, mesh=mesh)
    rep = rp.replay(mk(), feeds(), scfg(), arrival_substeps=2)
    rp.check_oracle(rep, mk)
    grows = [e for k, e in rep.log if k == 'grow']
    shrinks = [e for k, e in rep.log if k == 'shrink']
    migs = [e for k, e in rep.log if k == 'migrate']
    assert len(grows) >= 2 and len(shrinks) >= 1 and len(migs) == 3, (
        nd, grows, shrinks, migs)
    for tier, row in rep.tiers.items():
        assert row['offered'] == identity(row), (nd, tier, row)
    assert sum(r['migrated'] for r in rep.tiers.values()) == rep.migrated
    print(f'fleet mesh {nd}: OK ({rep.n_steps} deadlines, '
          f'{len(grows)} grows, {len(migs)} migrations)')

# multi-shard EDF: per-shard budgets + barrier re-sync, oracle-gated
mesh = make_host_mesh(2)
mk = lambda: TimeSurfaceEngine(cfg, mesh=mesh)
rep = rp.replay(mk(), feeds(), scfg(shard_budget=2, shard_barrier_every=4),
                arrival_substeps=2)
rp.check_oracle(rep, mk)
steps = [e for k, e in rep.log if k == 'step']
barriers = [i for i, e in enumerate(steps) if e.barrier]
assert barriers == [i for i in range(len(steps)) if (i + 1) % 4 == 0], (
    barriers)
assert any(e.overload for e in steps)
print(f'fleet EDF shards: OK ({len(barriers)} barriers)')
"""


@pytest.mark.slow
def test_fleet_mesh_sweep():
    """The fleet acceptance gate on emulated meshes: the elastic +
    migration churn schedule oracle-replays bitwise on a 1- and
    2-shard mesh, and the multi-shard EDF scheduler (per-shard budgets,
    barrier every 4 deadlines) stays a pure function of event
    timestamps — the recorded schedule replays, nothing re-derives."""
    import os
    import subprocess
    import sys
    import textwrap

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    inherited = os.environ.get("PYTHONPATH")
    env = dict(os.environ, PYTHONPATH=(
        src + os.pathsep + inherited if inherited else src))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_FLEET_MESH_SWEEP)],
        capture_output=True, text=True, env=env, timeout=1800,
    )
    assert out.returncode == 0, (
        f"fleet mesh sweep failed\nSTDOUT:\n{out.stdout}\n"
        f"STDERR:\n{out.stderr[-3000:]}"
    )
    assert "fleet mesh 2: OK" in out.stdout
    assert "fleet EDF shards: OK" in out.stdout
