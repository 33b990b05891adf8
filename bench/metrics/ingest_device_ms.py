"""ingest_device_ms: device milliseconds per deadline of the engine's
ingest programs (``serve/ts_engine.py`` ``push_staged``: the donated
scatter of the uploaded ring into the slot pool), summed from the
profiler trace by program name.  Moves ``events_per_s``."""

#: XLA program names of the ingest path
PROGRAMS = [r"ingest_step"]


def read(ctx):
    s = ctx.trace.module_s(PROGRAMS)
    if not ctx.steps or s is None:
        return None
    return s / len(ctx.steps) * 1e3
