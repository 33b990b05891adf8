"""harness_ms: host milliseconds per deadline that the benchmark's own
harness spends inside the window (``bench/harness.py``): slicing each
sensor's looping traffic into the deadline's arrival granules
(``bench.arrivals``) and copying the sampled products for the reference
(``bench.capture``).  No change to the program removes it; it shows how
much of ``events_per_s``'s wall time is the harness's."""


def read(ctx):
    if not ctx.steps:
        return None
    s = ctx.trace.span_s("bench.arrivals") + ctx.trace.span_s("bench.capture")
    if s <= 0:
        return None
    return s / len(ctx.steps) * 1e3
