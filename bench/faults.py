"""Faults planted in the timed path underneath a run, for the tests that
must see ``correct`` come out false and for the fault readings of
``calibrate.py``.  Each is a ``harness.Tap`` over the engine, or a patch
of the runtime's scheduler."""
import contextlib

import numpy as np

import harness


class Unchanged(harness.Tap):
    """Ingest returns the state unchanged: no event reaches a slot."""

    def push_staged(self, items):
        return None


class HalfBatch(harness.Tap):
    """Half of each ingest batch left out."""

    def push_staged(self, items):
        return self._engine.push_staged(list(items)[: len(items) // 2])


class Altered(harness.Tap):
    """One answer altered where it is produced: one cell of every
    product of every slot moves."""

    def read_many(self, specs, t_now=0.0, **kw):
        out = self._engine.read_many(specs, t_now, **kw)
        for prods in out.values():
            for name, a in prods.items():
                flat = a.reshape(a.shape[0], -1)
                prods[name] = flat.at[:, 0].add(
                    np.asarray(1, a.dtype)).reshape(a.shape)
        self.last = out
        return out


@contextlib.contextmanager
def unserved():
    """A due sensor never served: each deadline drops the last sensor
    the scheduler picked."""
    from repro.serve.stream import StreamRuntime

    orig = StreamRuntime._schedule

    def schedule(self, t):
        take, defer, overload, barrier = orig(self, t)
        return take[:-1], defer, overload, barrier

    StreamRuntime._schedule = schedule
    try:
        yield
    finally:
        StreamRuntime._schedule = orig


#: name -> (tap over the engine, context that patches the runtime)
FAULTS = {"none": (harness.Tap, contextlib.nullcontext),
          "unchanged": (Unchanged, contextlib.nullcontext),
          "half_batch": (HalfBatch, contextlib.nullcontext),
          "altered": (Altered, contextlib.nullcontext),
          "unserved": (harness.Tap, unserved)}
