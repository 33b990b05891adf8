"""read_device_ms: device milliseconds per deadline of the engine's read
programs (``ts_engine.read_many``: the fused spec reads of
``serve/spec.py``, decay, STCF, count and the heads of
``serve/heads.py``), summed from the profiler trace by program name.
Moves ``deadline_p95_ms``."""

#: XLA program names of the read path
PROGRAMS = [r"read_spec_products", r"read_head_products"]


def read(ctx):
    s = ctx.trace.module_s(PROGRAMS)
    if not ctx.steps or s is None:
        return None
    return s / len(ctx.steps) * 1e3
