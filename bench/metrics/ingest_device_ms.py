"""ingest_device_ms: device milliseconds per deadline and chip of the
engine's ingest programs (``serve/ts_engine.py`` ``push_staged``: the
donated scatter of the uploaded ring into the slot pool), summed from
the profiler trace by program name over the chips and divided by the
cell's chips, as ``device_idle_share`` averages over them.  Moves
``events_per_s``."""

from programs import INGEST


def read(ctx):
    s = ctx.trace.module_s(INGEST)
    if not ctx.steps or s is None:
        return None
    return s / ctx.chips / len(ctx.steps) * 1e3
