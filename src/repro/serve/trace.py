"""Profiler spans at the service's layer boundaries.

``span(name, **counts)`` opens a ``jax.profiler.TraceAnnotation``: while
a profiler trace is active (``jax.profiler.start_trace`` /
``stop_trace``, or a trace server) it records a host event on the same
clock as the device's operations, with ``counts`` stored as the event's
integer stats; with no trace active it costs about a microsecond.
Counts known only at the end of the work are added with
``set_metadata`` on the opened span::

    with span("serve.readback", deadline=k) as sp:
        ...
        sp.set_metadata(bytes=n)

The service opens its spans per phase of a deadline, never per sensor
or per event:

=================  ====================================  =============================
span               work                                  counts
=================  ====================================  =============================
``serve.step``     one ``StreamRuntime.step``            ``deadline``
``serve.schedule`` elastic shrink, EDF pick, deferrals   ``scheduled``, ``deferred``
``serve.coalesce`` drain queues into chunks, specs,      ``events``, ``chunks``
                   energy accounting
``serve.ingest``   one ``TimeSurfaceEngine.push_staged`` ``events``, ``rows``,
                                                         ``padded_rows``, ``capacity``
``serve.stage``    ring acquire and row fill             —
``serve.upload``   host-to-device transfer of the set    —
``serve.read``     enqueue of the read and head programs ``specs``
``serve.sync``     wait for a deadline's products        ``deadline``
``serve.readback`` device-to-host copies of them         ``deadline``, ``bytes``
``serve.digest``   SHA-256 replay digest of them, a      ``deadline``, ``bytes``,
                   tree of 1 MiB leaves on a thread pool ``leaves``, ``workers``
=================  ====================================  =============================

``deadline`` is the runtime's step index (``StepRecord.noise_step``):
``serve.sync``, ``serve.readback`` and ``serve.digest`` carry the index
of the deadline whose products they deliver, which in the pipelined
runtime is the previous one.
"""
from __future__ import annotations

import jax


def span(name: str, **counts: int) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` with integer ``counts``, as a context manager."""
    return jax.profiler.TraceAnnotation(name, **counts)
