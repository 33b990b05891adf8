"""host_offer_ms: host milliseconds per deadline spent handing events to
the sensors' bounded ingress queues (``StreamSensor.offer`` in
``serve/stream.py``), read from the benchmark's ``bench.offer`` spans in
the profiler trace.  Moves ``events_per_s``."""


def read(ctx):
    s = ctx.trace.span_s("bench.offer")
    if not ctx.steps or s <= 0:
        return None
    return s / len(ctx.steps) * 1e3
