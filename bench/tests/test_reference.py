"""The reference's schedule and queue model, and the comparison."""
import numpy as np

import reference as ref


def test_served_steps():
    assert ref.served_steps(0.01, 0.01, 5) == [1, 2, 3, 4, 5]
    # first deadline, then every multiple of the period
    assert ref.served_steps(0.05, 0.01, 16) == [1, 5, 10, 15]


def test_held_events_keep_the_newest_of_each_drain():
    t = np.array([0.001, 0.002, 0.003, 0.004, 0.011, 0.012, 0.013, 0.025])
    ev = (np.arange(8), np.zeros(8, int), t, np.zeros(8, int))
    x, _, tt, _ = ref.held_events(ev, [0.01, 0.02], queue_capacity=2)
    assert x.tolist() == [2, 3, 5, 6]          # 0.025 is not drained yet
    x, _, _, _ = ref.held_events(ev, [0.01, 0.02, 0.03], queue_capacity=8)
    assert x.tolist() == list(range(8))


def test_surface_count_and_support():
    import jax.numpy as jnp

    x = np.array([1, 1, 2]); y = np.array([0, 0, 3]); p = np.array([0, 1, 1])
    t = np.array([0.5, 0.25, 0.75], np.float32)
    sae = ref.active_surface(x, y, t, p, 4, 5, 2)
    assert sae[0, 0, 1] == 0.5 and sae[1, 0, 1] == 0.25 and sae[1, 3, 2] == 0.75
    assert np.isneginf(sae).sum() == 2 * 20 - 3
    assert ref.event_count(x, y, 4, 5, 1)[0, 1] == 1      # saturates at 1
    v = jnp.zeros((1, 5, 5)).at[0, 2, 2].set(1.0)
    sup = np.asarray(ref.stcf_support(v, 0.5, 1))
    assert sup[0, 2, 2] == 0 and sup[0, 1, 1] == 1 and sup.sum() == 8


def test_ulp_and_verdict():
    a = np.array([1.0, -0.0], np.float32)
    b = np.array([np.nextafter(np.float32(1), np.float32(2)), 0.0], np.float32)
    assert ref.ulp_distance(a, b) == 1
    ok, lines = ref.verdict({"surface_ulp": 3, "count_mismatch": 0},
                            {"surface_ulp": 4, "count_mismatch": 0})
    assert ok and lines == [("surface_ulp", 3, 4), ("count_mismatch", 0, 0)]
    assert not ref.verdict({"surface_ulp": 5}, {"surface_ulp": 4})[0]
    assert not ref.verdict({"logit_gap": 0.0}, {})[0]        # no limit


def _qvga():
    import json
    import os

    from conftest import BENCH

    return json.load(open(os.path.join(BENCH, "configs", "isc-qvga-tiered.json")))


def test_transient_fit_follows_the_published_points():
    c = _qvga()
    a1, tau1, a2, tau2, b = ref.transient_constants(c)
    assert 0 < tau1 <= tau2
    t, v = np.asarray(c["transient"]["points"]).T
    fitted = a1 * np.exp(-t / tau1) + a2 * np.exp(-t / tau2) + b
    assert np.abs(fitted - v).max() < 3e-3           # volts
    assert ref.fit_transient(c["transient"]) == (a1, tau1, a2, tau2, b)

