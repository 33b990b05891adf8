"""Seeded event traffic: the one general generator every traffic mix uses.

A traffic mix is a JSON file of parameters beside this module.  The
generator turns it, a configuration's fleet and a seed into one event
segment per sensor, ``segment_s`` long, which the harness loops with
timestamps shifted by the segment length, so a window of any length
needs no more generation.

Scene intensity fields and ``dvs_from_intensity`` are a copy of
``src/repro/events/synthetic.py`` at commit 589672e (log-intensity
threshold crossings, v2e-style), so the inputs of the benchmark stay put
when the program's own generator changes.

Mix parameters:

``segment_s``, ``fps``, ``noise_hz``, ``theta``
    segment length, simulator frame rate, background noise per pixel,
    contrast threshold;
``arrival_granules``
    offers per deadline and sensor;
``scenes``
    per tier, the scene kinds its sensors cycle through
    (``glyph``, ``driving``, ``hotel_bar``); every sensor simulates its
    own scene at the scene's own event rate;
``scene_seed``
    the simulated scenes come from this fixed seed, and the run's seed
    only reorders them: which sensor of a tier and scene kind carries
    which stream, and a per-sensor mirror of x, y and polarity.  Every
    seed then offers the same set of event counts per deadline, in
    another order, so runs of different seeds do the same work.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List

import numpy as np


# ----------------------------------------------------------------------------
# scenes (copied)
# ----------------------------------------------------------------------------

_GLYPHS = {  # 5x7 bitmap font for digit-like classes
    0: ["01110", "10001", "10011", "10101", "11001", "10001", "01110"],
    1: ["00100", "01100", "00100", "00100", "00100", "00100", "01110"],
    2: ["01110", "10001", "00001", "00110", "01000", "10000", "11111"],
    3: ["11111", "00010", "00100", "00010", "00001", "10001", "01110"],
    4: ["00010", "00110", "01010", "10010", "11111", "00010", "00010"],
    5: ["11111", "10000", "11110", "00001", "00001", "10001", "01110"],
    6: ["00110", "01000", "10000", "11110", "10001", "10001", "01110"],
    7: ["11111", "00001", "00010", "00100", "01000", "01000", "01000"],
    8: ["01110", "10001", "10001", "01110", "10001", "10001", "01110"],
    9: ["01110", "10001", "10001", "01111", "00001", "00010", "01100"],
}


def glyph_bitmap(cls: int, scale: int = 6) -> np.ndarray:
    rows = _GLYPHS[cls % 10]
    bm = np.array([[int(c) for c in row] for row in rows], np.float32)
    return np.kron(bm, np.ones((scale, scale), np.float32))


def moving_glyph_scene(h, w, cls, rng, saccade_hz=10.0, scale=6):
    """N-MNIST-like: a bright glyph under saccadic motion on dark background."""
    bm = glyph_bitmap(cls, scale)
    gh, gw = bm.shape
    if gh > h - 2 or gw > w - 2:  # shrink to fit small canvases
        scale = max(1, min((h - 2) // 7, (w - 2) // 5))
        bm = glyph_bitmap(cls, scale)
        gh, gw = bm.shape
    cx0 = rng.uniform(0, max(w - gw, 1))
    cy0 = rng.uniform(0, max(h - gh, 1))
    ax = rng.uniform(4, 10)
    ay = rng.uniform(4, 10)
    phase = rng.uniform(0, 2 * np.pi)

    def intensity(t: float) -> np.ndarray:
        img = np.full((h, w), 0.08, np.float32)
        dx = int(cx0 + ax * np.sin(2 * np.pi * saccade_hz * t + phase))
        dy = int(cy0 + ay * np.sin(4 * np.pi * saccade_hz * t))
        dx = int(np.clip(dx, 0, w - gw))
        dy = int(np.clip(dy, 0, h - gh))
        img[dy:dy + gh, dx:dx + gw] += bm * 0.9
        return img

    return intensity


def driving_scene(h, w, rng, speed_px_s=120.0, block=8):
    """DND21-'driving'-like: a translating piecewise-constant scene."""
    bh, bw = h // block + 2, (2 * w) // block + 2
    blocks = rng.uniform(0.1, 1.0, size=(bh, bw)).astype(np.float32)
    tex = np.kron(blocks, np.ones((block, block), np.float32))[:h, :2 * w]

    def intensity(t: float) -> np.ndarray:
        shift = int(speed_px_s * t) % w
        return tex[:, shift:shift + w]

    return intensity


def hotel_bar_scene(h, w, rng):
    """DND21-'hotel-bar'-like: static background, a few moving objects."""
    bg = rng.uniform(0.3, 0.5, size=(h, w)).astype(np.float32)
    obj = [
        dict(
            cx=rng.uniform(0.2 * w, 0.8 * w), cy=rng.uniform(0.2 * h, 0.8 * h),
            vx=rng.uniform(-60, 60), vy=rng.uniform(-30, 30),
            r=rng.uniform(4, 9), amp=rng.uniform(0.4, 0.6),
        )
        for _ in range(3)
    ]
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)

    def intensity(t: float) -> np.ndarray:
        img = bg.copy()
        for o in obj:
            cx = (o["cx"] + o["vx"] * t) % w
            cy = (o["cy"] + o["vy"] * t) % h
            d2 = (xx - cx) ** 2 + (yy - cy) ** 2
            img += o["amp"] * np.exp(-d2 / (2 * o["r"] ** 2)).astype(np.float32)
        return img

    return intensity


def dvs_from_intensity(intensity, h, w, duration, rng, theta=0.2, fps=1000.0,
                       noise_hz=0.0, eps=1e-3, max_events_per_px_per_step=4):
    """Emit +-theta log-intensity crossings with linear time interpolation,
    plus Poisson background noise at ``noise_hz`` per pixel.  Returns
    time-sorted ``(x, y, t, p)`` arrays (int32, int32, float32, int32)."""
    n_steps = int(round(duration * fps))
    dt = 1.0 / fps
    l_ref = np.log(intensity(0.0) + eps)
    xs, ys, tss, ps = [], [], [], []
    for s in range(1, n_steps + 1):
        t1 = s * dt
        l1 = np.log(intensity(t1) + eps)
        diff = l1 - l_ref
        k = np.floor(np.abs(diff) / theta).astype(np.int32)
        k = np.minimum(k, max_events_per_px_per_step)
        fired = k > 0
        if fired.any():
            yy, xx = np.nonzero(fired)
            kk = k[yy, xx]
            pol = (diff[yy, xx] > 0).astype(np.int32)
            reps = np.repeat(np.arange(len(yy)), kk)
            order = (np.concatenate([np.arange(c) for c in kk]) if len(kk)
                     else np.zeros(0, int))
            frac = ((order + 1).astype(np.float32)
                    / (kk[reps] + 1).astype(np.float32))
            tss.append((t1 - dt) + frac * dt)
            xs.append(xx[reps].astype(np.int32))
            ys.append(yy[reps].astype(np.int32))
            ps.append(pol[reps])
            l_ref[yy, xx] += np.sign(diff[yy, xx]) * kk * theta
    if noise_hz > 0:
        n_noise = rng.poisson(noise_hz * h * w * duration)
        xs.append(rng.integers(0, w, n_noise).astype(np.int32))
        ys.append(rng.integers(0, h, n_noise).astype(np.int32))
        tss.append(rng.uniform(0, duration, n_noise).astype(np.float32))
        ps.append(rng.integers(0, 2, n_noise).astype(np.int32))
    cat = lambda parts, dt_: (np.concatenate(parts).astype(dt_) if parts
                              else np.zeros(0, dt_))
    x, y = cat(xs, np.int32), cat(ys, np.int32)
    t, p = cat(tss, np.float32), cat(ps, np.int32)
    o = np.argsort(t, kind="stable")
    return x[o], y[o], t[o], p[o]


SCENES: Dict[str, Callable] = {
    "glyph": lambda h, w, rng, i: moving_glyph_scene(h, w, i % 10, rng),
    "driving": lambda h, w, rng, i: driving_scene(h, w, rng),
    "hotel_bar": lambda h, w, rng, i: hotel_bar_scene(h, w, rng),
}


# ----------------------------------------------------------------------------
# the general generator
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class SensorTraffic:
    """One sensor's looping segment: events sorted by ``t`` in
    ``[0, segment_s)``; loop ``m`` adds ``m * segment_s`` seconds."""

    tier: str
    scene: str
    x: np.ndarray
    y: np.ndarray
    t: np.ndarray   # float32 seconds within the segment
    p: np.ndarray
    segment_s: float

    @property
    def n(self) -> int:
        return int(self.x.shape[0])

    def times(self, loop: int) -> np.ndarray:
        """Absolute float64 timestamps of loop ``loop``."""
        return self.t.astype(np.float64) + loop * self.segment_s

    def before(self, t_end: float):
        """Every event with absolute time ``< t_end`` as ``(x, y, t64,
        p)``, loops concatenated in time order."""
        parts = []
        loop = 0
        while loop * self.segment_s < t_end:
            t = self.times(loop)
            hi = int(np.searchsorted(t, t_end, side="left"))
            parts.append((self.x[:hi], self.y[:hi], t[:hi], self.p[:hi]))
            loop += 1
        if not parts:
            return (np.zeros(0, np.int32), np.zeros(0, np.int32),
                    np.zeros(0, np.float64), np.zeros(0, np.int32))
        return tuple(np.concatenate([q[i] for q in parts]) for i in range(4))


#: stream tags that keep the seeded random streams apart
_SENSOR, _ORDER, _MIRROR = 0, 3, 4


def _rng(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng((int(seed) % 2 ** 63,) + key)


def fleet(config: dict):
    """``[(tier, index_in_tier)]`` in connection order (tier blocks, in
    the configuration's order)."""
    return [(tier["tier"], i) for tier in config["tiers"]
            for i in range(tier["sensors"])]


def _below(t: np.ndarray, segment_s: float) -> np.ndarray:
    """Keep float32 stamps inside ``[0, segment_s)`` after rounding."""
    return np.minimum(t, np.nextafter(np.float32(segment_s), np.float32(0)))


def _mirror(tr: SensorTraffic, h: int, w: int, rng) -> SensorTraffic:
    """Flip x, y and polarity, each with probability one half."""
    fx, fy, fp = rng.integers(0, 2, 3)
    return dataclasses.replace(
        tr, x=(w - 1 - tr.x) if fx else tr.x, y=(h - 1 - tr.y) if fy else tr.y,
        p=(1 - tr.p) if fp else tr.p)


def _reorder(out: List[SensorTraffic], config, seed) -> List[SensorTraffic]:
    """Give each sensor the stream of another sensor of its tier and scene
    kind, mirrored, as the run's seed says."""
    h, w = config["h"], config["w"]
    groups: Dict[tuple, List[int]] = {}
    for j, tr in enumerate(out):
        groups.setdefault((tr.tier, tr.scene), []).append(j)
    res = list(out)
    for g, (key, idx) in enumerate(sorted(groups.items())):
        perm = _rng(seed, _ORDER, g).permutation(idx)
        for dst, src in zip(idx, perm):
            res[dst] = _mirror(out[src], h, w, _rng(seed, _MIRROR, dst))
    return res


def generate(mix: dict, config: dict, seed: int) -> List[SensorTraffic]:
    """One ``SensorTraffic`` per sensor of ``config``'s fleet, in
    connection order."""
    h, w = config["h"], config["w"]
    seg = float(mix["segment_s"])
    sim = dict(theta=mix.get("theta", 0.2), fps=mix["fps"],
               noise_hz=mix.get("noise_hz", 0.0))
    tiers = [t["tier"] for t in config["tiers"]]
    out: List[SensorTraffic] = []
    for tier, i in fleet(config):
        rng = _rng(mix["scene_seed"], _SENSOR, tiers.index(tier), i)
        scenes = mix["scenes"][tier]
        kind = scenes[i % len(scenes)]
        x, y, t, p = dvs_from_intensity(SCENES[kind](h, w, rng, i), h, w,
                                        seg, rng, **sim)
        out.append(SensorTraffic(tier, kind, x, y, _below(t, seg), p,
                                 segment_s=seg))
    return _reorder(out, config, seed)
