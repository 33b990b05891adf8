"""Smoke run of the streaming time-surface service on TPU chips.

    python chip_smoke.py             # one chip: the paper's QVGA deployment
    python chip_smoke.py --chips 4   # the same pool sharded over four chips

One chip: builds the 3DS-ISC QVGA deployment (``configs/isc_qvga.py``:
240x320 arrays, two polarity planes, a pool of 512 slots) behind
``TimeSurfaceEngine`` with the compiled Pallas kernels, attaches 64
sensors of tiered mixed-scene traffic (``events.replay``) — a
``gesture`` tier served surface + STCF support + CNN class logits, a
``telemetry`` tier served surface + event count — warms up, serves 10 ms
deadlines through ``StreamRuntime``, then

* runs the bitwise replay oracle (``events.replay.check_oracle``);
* replays the same traffic through a second pair of engines in
  lockstep, the Pallas kernels against ``backend="ref"`` (plain jnp) on
  the same chip, and holds every served product to the bounds below;
* checks that the compiled read program holds Mosaic kernels
  (``tpu_custom_call``).

Four chips (``--chips 4``, and nothing else): the same deployment with the
pool sharded over a 4-chip mesh (``--mesh 4`` in the launchers), sensors
placed on all four chips by live migration at attach time and one more
``migrate()`` across chips mid-run, served through ``StreamRuntime``; the
log is replayed through a fresh sharded engine (its digests must equal
the served ones) in lockstep with a single-device engine, and every
product must match bit for bit except the CNN logits, which must agree
within ``LOGIT_RTOL``.

Everything runs in this one process (a chip belongs to one process).
Earlier lines report the device, per-phase wall times, compile seconds
and the comparisons; the last line is one JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure, including a host without a TPU, exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: Pallas-vs-ref bound on every float surface, in float32 ULPs.  Both
#: sides evaluate a1*exp(-dt/tau1) + a2*exp(-dt/tau2) + b, the Mosaic and
#: the XLA lowering of exp and divide each within a couple of ULPs; exp
#: scales an argument error by the argument, which stays below ~4 for the
#: dominant tau2 = 20 ms term over a 70 ms run (the tau1 = 0.7 ms term is
#: below b = 0.05 V long before its argument grows).
SURFACE_ULP = 8
#: STCF support counts must match exactly, except in the patch of a cell
#: whose reference voltage lies within SURFACE_ULP of v_tw (there the two
#: comparators may legitimately disagree); such cells are counted.
#: CNN logits, fed surfaces that differ by those ULPs, must agree within
#: this fraction of the largest |logit| of the read.
LOGIT_RTOL = 1e-3

N_SLOTS = 512
N_SENSORS = 64
DEADLINE_S = 0.01
DURATION_S = 0.07          # 7 deadlines served after the warm-up run
WARMUP_T_END = 0.03        # the warm-up serves the first 4 deadlines
SEED = 0


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def import_repro():
    """The ``repro`` package of this checkout, never another copy."""
    src = os.path.join(HERE, "src")
    sys.path.insert(0, src)
    try:
        import repro
    except ImportError as e:
        fail(f"no repro package next to this script ({src}): {e}")
    if not os.path.abspath(repro.__file__).startswith(src + os.sep):
        fail(f"imported repro from {repro.__file__}, not from {src}")


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from jax's own
    monitoring events."""

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

    def snapshot(self):
        return (self.seconds, self.compiles, self.cache_hits)


class Phases:
    def __init__(self, meter: CompileMeter):
        self.meter = meter

    def run(self, name, fn, *args, **kwargs):
        s0, c0, h0 = self.meter.snapshot()
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        wall = time.perf_counter() - t0
        s1, c1, h1 = self.meter.snapshot()
        print(f"phase {name}: wall {wall:.3f} s, compile {s1 - s0:.3f} s "
              f"over {c1 - c0} programs, cache hits {h1 - h0}; "
              f"{device_memory()}", flush=True)
        return out


def device_memory() -> str:
    """Live jax arrays, and bytes in use / peak on the fullest device."""
    import jax

    live = sum(a.nbytes for a in jax.live_arrays())
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    use = max(s.get("bytes_in_use", 0) for s in stats)
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    limit = min(s.get("bytes_limit", 0) for s in stats)
    return (f"live arrays {live / 2**30:.2f} GiB, device in use "
            f"{use / 2**30:.2f} GiB, peak {peak / 2**30:.2f} GiB of "
            f"{limit / 2**30:.2f} GiB")


# ----------------------------------------------------------------------------
# deployment and traffic
# ----------------------------------------------------------------------------

def deployment():
    from repro.configs.isc_qvga import CONFIG
    from repro.serve import spec as rs
    from repro.serve.ts_engine import TSEngineConfig

    gesture = rs.ReadoutSpec(surface=rs.surface(), stcf=rs.stcf(),
                             logits=rs.classify(inputs=("surface",)))
    telemetry = rs.ReadoutSpec(surface=rs.surface(), count=rs.count())
    # DVS pixels report ON and OFF events: one SAE plane per polarity
    cfg = TSEngineConfig(
        h=CONFIG.h, w=CONFIG.w, polarities=2, n_slots=N_SLOTS,
        chunk_capacity=2048, mode="edram", cmem_f=CONFIG.cmem_f,
        tau_tw=CONFIG.tau_tw, stcf_radius=CONFIG.stcf_radius,
        stcf_threshold=CONFIG.stcf_threshold, specs=(gesture, telemetry),
    )
    return cfg, gesture, telemetry


def traffic(cfg, gesture, telemetry, spread_over: int = 1):
    """Tiered mixed-scene feeds; with ``spread_over`` = k > 1 every
    sensor not on shard 0 migrates at attach to shard ``i % k``, and
    sensor 8 migrates from shard 0 to the last shard mid-run."""
    from repro.events import replay as rp

    feeds = rp.mixed_scene_feeds(cfg.h, cfg.w, DURATION_S, N_SENSORS,
                                 seed=SEED, tiered=True)
    per_shard = cfg.n_slots // spread_over
    out = []
    for i, f in enumerate(feeds):
        spec = gesture if f.qos.tier == "gesture" else telemetry
        move = None
        if spread_over > 1:
            if i % spread_over:
                move = (0.0, (i % spread_over) * per_shard + i)
            elif i == 8:
                move = (DURATION_S / 2, cfg.n_slots - 1)
        out.append(dataclasses.replace(
            f, qos=dataclasses.replace(f.qos, spec=spec), move=move))
    return out


# ----------------------------------------------------------------------------
# on-device comparisons (only scalars come back to the host)
# ----------------------------------------------------------------------------

def _ordered(x):
    import jax.numpy as jnp
    from jax import lax

    i = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(i < 0, jnp.int32(-2 ** 31) - i, i)


def _ulp(a, b):
    """Lexicographic float32 ULP distance (int32; opposite signs saturate)."""
    import jax.numpy as jnp

    ka, kb = _ordered(a), _ordered(b)
    same = (ka >= 0) == (kb >= 0)
    return jnp.where(same, jnp.abs(ka - kb), jnp.iinfo(jnp.int32).max)


@functools.lru_cache(maxsize=None)
def _kernels():
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    @jax.jit
    def surface_ulp(a, b):
        return _ulp(a, b).max()

    @functools.partial(jax.jit, static_argnames=("radius", "v_tw"))
    def stcf_check(sup_a, sup_b, v_ref, radius, v_tw):
        near = _ulp(v_ref, jnp.float32(v_tw)) <= SURFACE_ULP
        reach = ops.stcf_support(near, radius=radius, include_self=True,
                                 backend="ref") > 0
        mism = sup_a != sup_b
        return near.sum(), mism.sum(), (mism & ~reach).sum()

    @jax.jit
    def logit_check(a, b):
        return jnp.abs(a - b).max(), jnp.abs(b).max()

    @jax.jit
    def bits_differ(a, b):
        if jnp.issubdtype(a.dtype, jnp.floating):
            a = jax.lax.bitcast_convert_type(a, jnp.int32)
            b = jax.lax.bitcast_convert_type(b, jnp.int32)
        return (a != b).sum()

    return surface_ulp, stcf_check, logit_check, bits_differ


def lockstep(log, eng_a, eng_b, on_step):
    """Apply a runtime's action log to two fresh engines side by side and
    hand every step's reads of both to ``on_step(rec, [(spec, a, b)])``."""
    for kind, entry in log:
        if kind == "attach":
            slot, qos = entry
            for eng in (eng_a, eng_b):
                got = eng.attach(qos=qos).slot
                check(got == slot, f"lockstep attach landed on {got}, "
                                   f"log says {slot}")
        elif kind == "migrate":
            for eng in (eng_a, eng_b):
                eng.migrate(*entry)
        elif kind == "step":
            if entry.chunks:
                for eng in (eng_a, eng_b):
                    eng.push_staged(entry.chunks)
            on_step(entry, [(sp, eng_a.read(sp, entry.t_read),
                             eng_b.read(sp, entry.t_read))
                            for sp in entry.specs])
        else:
            fail(f"lockstep cannot replay a {kind!r} log entry")


# ----------------------------------------------------------------------------
# one chip
# ----------------------------------------------------------------------------

def one_chip(phases: Phases):
    import jax.numpy as jnp

    from repro.events import replay as rp
    from repro.kernels import ops
    from repro.serve import heads as heads_mod
    from repro.serve import spec as rs
    from repro.serve import ts_engine as te
    from repro.serve.stream import StreamConfig

    cfg, gesture, telemetry = deployment()
    feeds = phases.run("traffic", traffic, cfg, gesture, telemetry)
    n_events = sum(f.stream.n for f in feeds)
    tiers = {}
    for f in feeds:
        tiers[f.qos.tier] = tiers.get(f.qos.tier, 0) + 1
    print(f"deployment: {cfg.h}x{cfg.w}x{cfg.polarities}, {cfg.n_slots} "
          f"slots, {len(feeds)} sensors {tiers}, {n_events} events over "
          f"{DURATION_S} s", flush=True)
    scfg = StreamConfig(deadline_s=DEADLINE_S)

    def serve(t_end=None):
        eng = te.TimeSurfaceEngine(cfg)
        report = rp.replay(eng, feeds, scfg, telemetry, t_end=t_end)
        return eng, report

    warm = phases.run("warmup", serve, WARMUP_T_END)[1]
    eng, report = phases.run("serve", serve)
    st = eng.stats()
    check(st["backend"] == "pallas",
          f"engine resolved kernel backend {st['backend']!r}, not 'pallas'")
    check(report.n_steps >= 5, f"served only {report.n_steps} deadlines")
    check(report.ingested > 0, "no event was ingested")
    print(f"served {report.n_steps} deadlines of {DEADLINE_S * 1e3:.0f} ms "
          f"(after {warm.n_steps} warm-up): offered {report.offered}, "
          f"ingested {report.ingested}, dropped {report.dropped}; host wall "
          f"{report.wall_s:.3f} s, dispatch->sync p50 "
          f"{report.latency_p50_us} us p99 {report.latency_p99_us} us",
          flush=True)

    # the compiled read program must hold the Mosaic kernels
    def compiled_read_text():
        dyn = rs.resolve_dynamic(gesture, cfg)
        statics = rs.resolve_static(gesture, cfg)
        hp = {n: heads_mod.resolve_head_params(h, cfg)
              for n, h in gesture.head_products()}
        return te.read_spec_products.lower(
            eng.state.surfaces.sae, eng.state.counts, jnp.float32(0.0), dyn,
            spec=gesture, cfg=cfg, backend=st["backend"], statics=statics,
            head_params=hp,
        ).compile().as_text()

    text = phases.run("compile_check", compiled_read_text)
    n_kernels = text.count("tpu_custom_call")
    check(n_kernels > 0, "compiled read program has no tpu_custom_call")
    print(f"compiled gesture read program: {n_kernels} tpu_custom_call "
          "sites", flush=True)
    del eng

    n_oracle = phases.run(
        "oracle", rp.check_oracle, report, lambda: te.TimeSurfaceEngine(cfg),
        telemetry)
    print(f"bitwise replay oracle: OK over {n_oracle} deadlines", flush=True)

    # Pallas kernels vs the plain-jnp reference, same traffic, same chip
    surface_ulp, stcf_check, logit_check, _ = _kernels()
    ref_cfg = dataclasses.replace(cfg, backend="ref")
    worst = {"surface_ulp": 0, "near_cells": 0, "stcf_mismatch": 0,
             "stcf_unexplained": 0, "count_mismatch": 0, "logit_rel": 0.0}
    statics = {sp: dict(rs.resolve_static(sp, cfg))
               for sp in (gesture, telemetry)}
    dynamic = {sp: rs.resolve_dynamic(sp, cfg) for sp in (gesture, telemetry)}
    eng_ref = None

    def on_step(rec, reads):
        for sp, got, want in reads:
            for name, prod in sp.products:
                a, b = got[name], want[name]
                if isinstance(prod, rs.Surface):
                    worst["surface_ulp"] = max(worst["surface_ulp"],
                                               int(surface_ulp(a, b)))
                elif isinstance(prod, rs.Stcf):
                    v = ops.ts_decay(eng_ref.state.surfaces.sae,
                                     jnp.float32(rec.t_read),
                                     dynamic[sp][name], backend="ref")
                    radius = (prod.radius if prod.radius is not None
                              else cfg.stcf_radius)
                    near, mism, bad = (int(x) for x in stcf_check(
                        a, b, v, radius=radius, v_tw=statics[sp][name]))
                    worst["near_cells"] += near
                    worst["stcf_mismatch"] += mism
                    worst["stcf_unexplained"] += bad
                elif isinstance(prod, rs.Count):
                    worst["count_mismatch"] += int((a != b).sum())
                elif isinstance(prod, rs.Classify):
                    diff, scale = (float(x) for x in logit_check(a, b))
                    worst["logit_rel"] = max(worst["logit_rel"],
                                             diff / max(1.0, scale))

    def compare():
        nonlocal eng_ref
        eng_ref = te.TimeSurfaceEngine(ref_cfg)
        lockstep(report.log, te.TimeSurfaceEngine(cfg), eng_ref, on_step)

    phases.run("pallas_vs_ref", compare)
    print(f"pallas vs ref over {report.n_steps} deadlines: surface max "
          f"{worst['surface_ulp']} ULP (bound {SURFACE_ULP}); stcf "
          f"{worst['stcf_mismatch']} differing support cells, "
          f"{worst['stcf_unexplained']} outside the patch of the "
          f"{worst['near_cells']} cells within {SURFACE_ULP} ULP of v_tw; "
          f"count {worst['count_mismatch']} differing cells; logits max "
          f"|diff| {worst['logit_rel']:.3g} of the largest |logit| "
          f"(bound {LOGIT_RTOL})", flush=True)
    check(worst["surface_ulp"] <= SURFACE_ULP, "surface beyond the ULP bound")
    check(worst["stcf_unexplained"] == 0,
          "stcf support differs away from near-threshold cells")
    check(worst["count_mismatch"] == 0, "event counts differ")
    check(worst["logit_rel"] <= LOGIT_RTOL, "logits beyond the bound")
    del eng_ref


# ----------------------------------------------------------------------------
# four chips
# ----------------------------------------------------------------------------

def four_chips(phases: Phases):
    import jax
    import numpy as np

    from repro.events import replay as rp
    from repro.launch.mesh import make_host_mesh
    from repro.serve import spec as rs
    from repro.serve import ts_engine as te
    from repro.serve.stream import StreamConfig, digest_step

    cfg, gesture, telemetry = deployment()
    mesh = make_host_mesh(4)
    feeds = phases.run("traffic", traffic, cfg, gesture, telemetry, 4)
    scfg = StreamConfig(deadline_s=DEADLINE_S)

    def serve():
        eng = te.TimeSurfaceEngine(cfg, mesh=mesh)
        return eng, rp.replay(eng, feeds, scfg, telemetry)

    eng, report = phases.run("serve_sharded", serve)
    st = eng.stats()
    check(st["backend"] == "pallas",
          f"engine resolved kernel backend {st['backend']!r}, not 'pallas'")
    per_shard = cfg.n_slots // 4
    first_step = next(i for i, (k, _) in enumerate(report.log)
                      if k == "step")
    moves = [(i > first_step, e) for i, (k, e) in enumerate(report.log)
             if k == "migrate"]
    cross = [(s, d) for _, (s, d) in moves
             if s // per_shard != d // per_shard]
    mid_run = [(s, d) for late, (s, d) in moves if late]
    check(any(s // per_shard != d // per_shard for s, d in mid_run),
          "no migrate() across chips after serving began")
    n_ev = eng.state.surfaces.n_events
    placement = {}
    for shard in n_ev.addressable_shards:
        rows = np.asarray(shard.data)
        placement[str(shard.device)] = (int((rows > 0).sum()),
                                        int(rows.sum()))
    print(f"sharded engine: {st['mesh']}; {len(cross)} of {len(moves)} "
          f"migrations crossed chips (mid-run: {mid_run}); per device "
          f"(slots holding events, events): {placement}", flush=True)
    check(len(placement) == 4 and all(n > 0 for n, _ in placement.values()),
          "slots did not land on all four devices")
    del eng

    _, _, logit_check, bits_differ = _kernels()
    dev0 = jax.devices()[0]
    digests, stats = [], {"stage0_bits_differ": 0, "logit_bits_differ": 0,
                          "logit_rel": 0.0}

    def on_step(rec, reads):
        digests.append(digest_step([a for _, a, _ in reads]))
        for sp, a_all, b_all in reads:
            for name, prod in sp.products:
                a = jax.device_put(a_all[name], dev0)
                b = b_all[name]
                n = int(bits_differ(a, b))
                if isinstance(prod, rs.Classify):
                    stats["logit_bits_differ"] += n
                    diff, scale = (float(x) for x in logit_check(a, b))
                    stats["logit_rel"] = max(stats["logit_rel"],
                                             diff / max(1.0, scale))
                else:
                    stats["stage0_bits_differ"] += n

    phases.run("sharded_vs_single", lockstep, report.log,
               te.TimeSurfaceEngine(cfg, mesh=mesh), te.TimeSurfaceEngine(cfg),
               on_step)
    check(digests == report.digests,
          "fresh sharded replay digests != served digests")
    print(f"sharded (4 chips) vs single-device over {len(digests)} "
          f"deadlines: served digests replayed bitwise; stage-0 products "
          f"{stats['stage0_bits_differ']} differing cells; logits "
          f"{stats['logit_bits_differ']} differing values, max |diff| "
          f"{stats['logit_rel']:.3g} of the largest |logit| (bound "
          f"{LOGIT_RTOL})", flush=True)
    check(stats["stage0_bits_differ"] == 0,
          "sharded stage-0 products are not bitwise the single-device ones")
    check(stats["logit_rel"] <= LOGIT_RTOL, "sharded logits beyond the bound")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip deployment and its checks; 4: "
                         "only the 4-chip sharded pool against one chip")
    args = ap.parse_args()

    import_repro()
    from repro import platform as pf

    cache_dir = pf.use_compile_cache()
    import jax

    meter = CompileMeter()
    backend = jax.default_backend()
    check(backend == "tpu", f"no TPU found (jax platform {backend!r})")
    devices = jax.devices()
    check(len(devices) >= args.chips,
          f"{args.chips} chips asked for, {len(devices)} found")
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; jax "
          f"{jax.__version__}; compile cache {cache_dir}", flush=True)

    phases = Phases(meter)
    if args.chips == 4:
        four_chips(phases)
    else:
        one_chip(phases)
    s, c, h = meter.snapshot()
    print(f"compile seconds: {s:.3f} over {c} programs, persistent cache "
          f"hits {h}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
