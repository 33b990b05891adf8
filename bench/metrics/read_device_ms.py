"""read_device_ms: device milliseconds per deadline and chip of the
engine's read programs (``ts_engine.read_many``: the fused spec reads of
``serve/spec.py``, decay, STCF, count and the heads of
``serve/heads.py``), summed from the profiler trace by program name
over the chips and divided by the cell's chips.  Moves
``deadline_p95_ms``."""

from programs import READ


def read(ctx):
    s = ctx.trace.module_s(READ)
    if not ctx.steps or s is None:
        return None
    return s / ctx.chips / len(ctx.steps) * 1e3
