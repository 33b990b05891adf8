"""Multi-sensor streaming demo: four event cameras share one engine.

The session/spec API end to end: each camera holds a ``SensorSession``
(no raw slot ints), and every window deadline serves one composed
``ReadoutSpec`` — decayed surface + comparator mask + event count — from
a single fused dispatch.  AER chunks arrive interleaved in 20 ms windows
through the fused ``serve_step`` path: events reach the engine in two
half-window bursts, the first read is a dense fill, and the second
re-reads only the dirty tiles the late burst touched.  Mid-run, sensor 1
disconnects (``detach``) and a new camera reuses its slot (fresh surface
and counter plane, no retrace, cache stays coherent).  A model section
then serves stage-1 heads — CNN class logits and STCF denoise labels —
fused into the same dispatch as the surfaces, bitwise equal to the
standalone head.  A final section replays the same scene mix as
*continuous* traffic through the ``StreamRuntime`` (bounded queues,
deadline coalescing, pipelined dispatch, a logits-bearing gesture tier)
and gates it bitwise against a synchronous oracle.

    PYTHONPATH=src python examples/serve_sensors.py
    PYTHONPATH=src python examples/serve_sensors.py --mesh 2   # sharded pool
"""
import argparse

import numpy as np

H, W = 64, 86
WINDOW_S = 0.02
DURATION = 0.2


def window(s, lo: float, hi: float) -> np.ndarray:
    from repro.events import aer

    return aer.pack(s.window(lo, hi))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="shard the slot pool over N devices (TPU chips, "
                         "or emulated host devices on CPU)")
    args = ap.parse_args()

    # mesh setup must precede any jax device use (host-device emulation)
    mesh = None
    if args.mesh:
        from repro import platform as pf
        from repro.launch import mesh as mesh_mod

        pf.request_mesh_devices(args.mesh)
        mesh = mesh_mod.make_host_mesh(args.mesh)

    from repro.events import datasets
    from repro.serve import spec as rs
    from repro.serve.ts_engine import TSEngineConfig, TimeSurfaceEngine

    FRAME = rs.ReadoutSpec(surface=rs.surface(), mask=rs.mask(),
                           count=rs.count(4))
    cfg = TSEngineConfig(h=H, w=W, n_slots=4, chunk_capacity=4096,
                         mode="edram", specs=(FRAME,))
    eng = TimeSurfaceEngine(cfg, mesh=mesh)
    if mesh is not None:
        print(f"slot pool sharded over {dict(mesh.shape)} "
              f"({eng.n_slots_padded} slots incl. padding)")

    kinds = ["driving", "driving", "hotel_bar", "hotel_bar"]
    streams = [
        datasets.dnd21_like(k, h=H, w=W, duration=DURATION, seed=i)
        for i, k in enumerate(kinds)
    ]
    cams = [eng.attach() for _ in streams]
    print(f"{len(streams)} sensors on slots {[c.slot for c in cams]}: "
          f"{[s.n for s in streams]} events")

    n_win = int(round(DURATION / WINDOW_S))
    for wi in range(n_win):
        lo, hi = wi * WINDOW_S, (wi + 1) * WINDOW_S

        if wi == n_win // 2:  # sensor 1 disconnects; a new one takes the slot
            cams[1].detach()
            cams[1] = eng.attach()
            streams[1] = datasets.dnd21_like("hotel_bar", h=H, w=W,
                                             duration=DURATION, seed=99)
            print(f"window {wi}: sensor 1 swapped (slot {cams[1].slot} "
                  f"reused, generation {cams[1].generation})")

        # two half-window bursts, both rendered at the window deadline:
        # burst 1 refills the cache densely (t_now moved), burst 2 only
        # re-reads the tiles it dirtied; mask and count ride the same
        # fused dispatch
        mid = lo + WINDOW_S / 2
        for b_lo, b_hi in ((lo, mid), (mid, hi)):
            items = [(cam, window(s, b_lo, b_hi))
                     for cam, s in zip(cams, streams)]
            frame = eng.serve_step(items, FRAME, hi)
        occ = np.asarray(frame["mask"]).mean(axis=(1, 2, 3))
        active = (np.asarray(frame["count"]) > 0).sum(axis=(1, 2))
        print(f"t={hi*1e3:5.0f} ms  occupancy per slot: "
              + "  ".join(f"{occ[c.slot]:.3f}" for c in cams)
              + "   active px: "
              + " ".join(f"{active[c.slot]:5d}" for c in cams))

    stats = eng.stats()
    print("final events per slot:",
          [stats["n_events"][c.slot] for c in cams])

    # -- stage-1 model heads: logits out of the same fused dispatch ----------
    # a head-bearing spec serves model outputs end to end: the CNN
    # classifier consumes the surface product (through an optimization
    # barrier, so fusing it cannot perturb the surface bits) and the
    # denoise head thresholds the STCF support map — same dispatch, same
    # jit cache key, weights resolved once from the spec's static key
    import jax

    from repro.models import cnn
    from repro.models.frontends import ts_stack_frontend
    from repro.serve import heads as heads_mod

    head = rs.classify(n_classes=4, width=16)
    MODEL = rs.ReadoutSpec(surface=rs.surface(), stcf=rs.stcf(),
                           logits=head, labels=rs.denoise())
    out = eng.read(MODEL, DURATION)
    lg = np.asarray(out["logits"])
    print("\nmodel products (classify + denoise fused with the surface):")
    for c in cams:
        keep = float(np.asarray(out["labels"])[c.slot].mean())
        print(f"  slot {c.slot}: class {int(lg[c.slot].argmax())}, "
              f"logits {np.array2string(lg[c.slot], precision=2)}, "
              f"denoise keep {keep:.3f}")
    params = heads_mod.resolve_head_params(head, cfg)
    want = jax.jit(lambda p, s: cnn.cnn_apply(p, ts_stack_frontend([s])))(
        params, out["surface"])
    same = bool((lg == np.asarray(want)).all())
    print(f"  fused logits bitwise equal standalone cnn_apply: {same}")
    assert same

    # -- the same traffic as *continuous* streaming ---------------------------
    # the request/response loop above hand-windows the streams; the
    # StreamRuntime does it as sustained traffic: bounded ingress queues,
    # deadline-coalesced chunks, pipelined dispatch (one host sync per
    # deadline), and a bitwise synchronous-oracle gate over the replay
    from repro.events import replay as rp
    from repro.serve.stream import StreamConfig

    print("\nstreaming replay (drop_oldest, churn):")
    feeds = rp.mixed_scene_feeds(H, W, DURATION, 4, seed=5, churn=True)
    scfg = StreamConfig(policy="drop_oldest", queue_capacity=4096,
                        deadline_s=WINDOW_S)
    report = rp.replay(TimeSurfaceEngine(cfg, mesh=mesh), feeds, scfg,
                       rs.SURFACE_SPEC)
    print(report.summary())
    n = rp.check_oracle(report, lambda: TimeSurfaceEngine(cfg, mesh=mesh),
                        rs.SURFACE_SPEC)
    print(f"bitwise oracle gate: OK over {n} deadlines")

    # -- QoS: priority tiers under an overloaded step budget ------------------
    # the same scene mix, but glyph sensors connect as the `gesture`
    # tier (priority 0, 250ms p99 SLO) and the rest as `telemetry`
    # (priority 2); the chunk budget covers only the gesture tier's
    # demand, so every deadline is overloaded and priority preempts
    # EDF — gesture is always served and holds its SLO while
    # telemetry's queues absorb the deferrals and drops, and the
    # per-tier counters conserve exactly.  Scheduling is still pure
    # virtual time: the run replays bitwise as before.
    # the gesture tier additionally carries a head-bearing per-tier
    # spec: its sensors stream CNN logits every deadline, digest-chained
    # into the same bitwise oracle gate as the surfaces
    print("\nQoS tiers (gesture preempts telemetry, step budget 8):")
    import dataclasses

    feeds = rp.mixed_scene_feeds(H, W, DURATION, 4, seed=5, tiered=True)
    gesture_spec = rs.ReadoutSpec(surface=rs.surface(), logits=head)
    for f in feeds:
        if f.qos.tier == "gesture":
            f.qos = dataclasses.replace(f.qos, spec=gesture_spec)
    scfg = StreamConfig(policy="drop_oldest", queue_capacity=1 << 15,
                        deadline_s=WINDOW_S, step_chunk_budget=8)
    # warmup on a throwaway engine: jit-compiles the QoS section's
    # dispatch shapes so the latency percentiles below measure
    # scheduling, not compilation
    rp.replay(TimeSurfaceEngine(cfg, mesh=mesh), feeds, scfg,
              rs.SURFACE_SPEC)
    report = rp.replay(TimeSurfaceEngine(cfg, mesh=mesh), feeds, scfg,
                       rs.SURFACE_SPEC)
    print(report.summary())
    for tier, row in sorted(report.tiers.items()):
        assert row["offered"] == (
            row["ingested"] + row["dropped"] + row["refused"]
            + row["discarded"] + row["deferred"]
        ), f"per-tier conservation broken for {tier}"
    print("per-tier conservation: exact")
    n = rp.check_oracle(report, lambda: TimeSurfaceEngine(cfg, mesh=mesh),
                        rs.SURFACE_SPEC)
    print(f"bitwise oracle gate: OK over {n} deadlines")


if __name__ == "__main__":
    main()
