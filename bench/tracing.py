"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` turns the JAX profiler's ``.xplane.pb`` into a small plain
structure (the same one the tests record as JSON)::

    {"devices": {plane: {"ops": [[name, start_ns, dur_ns, program, mosaic]],
                         "modules": [[program, start_ns, dur_ns]]}},
     "spans": [[name, start_ns, dur_ns]]}

``devices`` holds each chip's XLA operations and XLA programs
("modules"): an operation's ``name`` is its HLO name (``fusion.4``),
``program`` the jitted program it ran in (``jit_read_spec_products``),
``mosaic`` 1 for a Pallas kernel (a ``tpu_custom_call``).  ``spans``
holds the host spans the benchmark opened (names starting ``bench.``).
Everything else in the trace is dropped.
``Trace`` then answers what the metrics ask: busy and idle time per
device inside the traced window, time per program or operation name,
and the longest idle gaps with the host span that was open in each.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

#: the benchmark's own host spans
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


#: the planes of the chips (not the host, not the collectives' plane)
_CHIP = re.compile(r"^/device:TPU:\d+$")


def _program(name: str) -> str:
    return name.split("(")[0]


def normalize(ops, modules) -> dict:
    """One chip's raw ``(hlo_text, start, dur)`` operations and
    ``(module, start, dur)`` programs as the plain structure: each
    operation named by its HLO name and placed in the program whose
    interval holds its start."""
    mods = sorted(([_program(n), s, d] for n, s, d in modules),
                  key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for text, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][0] if i >= 0 and s < mods[i][1] + mods[i][2] else ""
        name = text.split(" = ")[0].lstrip("%")
        out.append([name, s, d, prog, int("tpu_custom_call" in text)])
    return {"ops": out, "modules": mods}


def load(directory: str) -> dict:
    """Read the one ``.xplane.pb`` the profiler wrote under ``directory``."""
    import jax

    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: Dict[str, dict] = {}
    spans: List[list] = []
    for plane in data.planes:
        if _CHIP.match(plane.name):
            raw = {"XLA Ops": [], "XLA Modules": []}
            for line in plane.lines:
                if line.name in raw:
                    raw[line.name] += [(ev.name, ev.start_ns, ev.duration_ns)
                                       for ev in line.events]
            devices[plane.name] = normalize(raw["XLA Ops"], raw["XLA Modules"])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"devices": devices, "spans": spans}


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class Trace:
    """Queries over one loaded trace, restricted to the traced window."""

    def __init__(self, data: dict):
        self.data = data
        win = [s for s in data["spans"] if s[0] == WINDOW_SPAN]
        if not win:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        _, start, dur = max(win, key=lambda s: s[2])
        self.lo, self.hi = float(start), float(start + dur)
        self.devices = sorted(data["devices"])

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def _ops(self, dev: str):
        return self.data["devices"][dev]["ops"]

    def busy(self, dev: str) -> List[Tuple[float, float]]:
        """Disjoint intervals in which some operation ran on ``dev``."""
        return union(clip([(o[1], o[1] + o[2]) for o in self._ops(dev)],
                          self.lo, self.hi))

    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(sum(e - s for s, e in self.busy(d))
                   for d in self.devices) / len(self.devices) * 1e-9

    def span_s(self, name: str) -> float:
        """Seconds inside the window covered by host spans ``name``."""
        return sum(e - s for s, e in clip(
            [(s, s + d) for n, s, d in self.data["spans"] if n == name],
            self.lo, self.hi)) * 1e-9

    def module_s(self, patterns: Sequence[str]) -> Optional[float]:
        """Device seconds of programs whose name matches a pattern,
        summed over devices; ``None`` when none ran in the window."""
        rx = re.compile("|".join(patterns))
        hits = [(s, s + d) for dev in self.devices
                for n, s, d in self.data["devices"][dev]["modules"]
                if rx.search(n)]
        if not hits:
            return None
        return sum(e - s for s, e in clip(hits, self.lo, self.hi)) * 1e-9

    def op_s(self, programs: Sequence[str], mosaic: bool = False) -> Optional[float]:
        """Device seconds of the operations inside programs whose name
        matches a pattern (only Pallas kernels with ``mosaic``)."""
        rx = re.compile("|".join(programs))
        hits = [(o[1], o[1] + o[2]) for dev in self.devices
                for o in self._ops(dev)
                if rx.search(o[3]) and (o[4] or not mosaic)]
        if not hits:
            return None
        return sum(e - s for s, e in clip(hits, self.lo, self.hi)) * 1e-9

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` operations (``program/op``) that took the most
        device time."""
        tot: Dict[str, float] = {}
        for dev in self.devices:
            for name, s, d, prog, _ in self._ops(dev):
                for cs, ce in clip([(s, s + d)], self.lo, self.hi):
                    key = f"{prog}/{name}"
                    tot[key] = tot.get(key, 0.0) + (ce - cs) * 1e-9
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The ``n`` longest idle gaps of the first device, each named by
        the benchmark span (other than the window) that overlaps it most."""
        if not self.devices:
            return []
        busy = self.busy(self.devices[0])
        gaps, t = [], self.lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.hi:
            gaps.append((t, self.hi))
        spans = [(nm, s, s + d) for nm, s, d in self.data["spans"]
                 if nm != WINDOW_SPAN]
        out = []
        for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            best, label = 0.0, "none"
            for nm, s, e in spans:
                ov = min(e, ge) - max(s, gs)
                if ov > best:
                    best, label = ov, nm
            out.append([label, (ge - gs) * 1e-9])
        return out
