"""The traffic copy: one seed gives identical events, two seeds differ,
and every seed offers the same events per deadline, reordered."""
import json
import os

import numpy as np
import pytest

import generator
from conftest import BENCH

FLEET = {"h": 32, "w": 40,
         "tiers": [{"tier": "gesture", "sensors": 4},
                   {"tier": "telemetry", "sensors": 12}]}


def mix(name):
    return json.load(open(os.path.join(BENCH, "traffic", name + ".json")))


def same(a, b):
    return all(np.array_equal(getattr(x, f), getattr(y, f))
               for x, y in zip(a, b) for f in ("x", "y", "t", "p"))


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_seed_fixes_the_events(seed):
    m = mix("tiered_scenes")
    a = generator.generate(m, FLEET, seed)
    b = generator.generate(m, FLEET, seed)
    c = generator.generate(m, FLEET, seed + 1)
    assert len(a) == 16 and same(a, b)
    assert not same(a, c)
    for tr in a:
        assert tr.n and np.all(np.diff(tr.t) >= 0)
        assert tr.t[-1] < m["segment_s"] and tr.x.max() < 40 and tr.y.max() < 32


def test_looped_events_before():
    m = mix("tiered_scenes")
    tr = generator.generate(m, FLEET, 3)[0]
    seg = m["segment_s"]
    x, y, t, p = tr.before(2.5 * seg)
    assert len(t) == 2 * tr.n + int(np.sum(tr.t < 0.5 * seg))
    assert np.all(np.diff(t) >= 0) and t[-1] < 2.5 * seg


def test_fixed_scenes_give_every_seed_the_same_sizes():
    m = mix("tiered_scenes")
    a = generator.generate(m, FLEET, 10)
    b = generator.generate(m, FLEET, 11)
    for tier in ("gesture", "telemetry"):
        assert (sorted(t.n for t in a if t.tier == tier)
                == sorted(t.n for t in b if t.tier == tier))
    per = lambda trs: sum(np.bincount((t.t / 0.01).astype(int), minlength=10)
                          for t in trs)
    assert np.array_equal(per(a), per(b))


def test_a_mix_states_its_scene_seed():
    m = mix("tiered_scenes")
    del m["scene_seed"]
    with pytest.raises(KeyError):
        generator.generate(m, FLEET, 1)
