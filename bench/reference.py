"""Plain reference of the served products, and the comparison that
decides ``correct``.

Written from the configuration file alone: it imports nothing of the
program under test and takes nothing it made.  For one sensor and one
deadline it rebuilds, from the events the harness offered,

* which events the sensor's bounded queue kept: the queue is drained at
  each of the sensor's deadlines and ``drop_oldest`` keeps the newest
  ``queue_capacity`` events offered since the previous drain;
* the surface of active events (latest timestamp per cell and polarity);
* the decayed surface ``a1 exp(-dt/tau1) + a2 exp(-dt/tau2) + b``
  (0 where never written), with the constants fitted here to the cell's
  published transient points by the procedure the configuration states,
  the STCF support map (cells of the ``(2r+1)^2`` patch, the centre
  excluded, whose voltage is above the voltage the transient reaches
  after ``tau_tw``), the saturating polarity-merged event count, and
  the GoogLeNet-style head's logits.

``dtype`` selects the precision: float32 (matmuls at ``highest``) is the
reference; bfloat16 is the control that every limit must reject.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict, List, Sequence

import numpy as np


# ----------------------------------------------------------------------------
# schedule and queue
# ----------------------------------------------------------------------------

def served_steps(period_s: float, deadline_s: float, n_steps: int) -> List[int]:
    """Deadlines (1-based step numbers on the ``deadline_s`` grid) at
    which a sensor of ``period_s`` is served: the first deadline, then
    each deadline that reaches the next multiple of its period."""
    out, nxt = [], -math.inf
    for k in range(1, n_steps + 1):
        t = k * deadline_s
        if nxt <= t + 1e-9:
            out.append(k)
            nxt = (math.floor((t + 1e-9) / period_s) + 1) * period_s
    return out


def held_events(events, drain_deadlines: Sequence[float], queue_capacity: int):
    """The events a sensor's products hold once it has been drained at
    each of ``drain_deadlines`` (ascending): events offered before a
    drain enter it, and of each drain only the newest
    ``queue_capacity`` survive.  ``events`` is ``(x, y, t64, p)`` sorted
    by time; returns the kept subset in the same layout."""
    x, y, t, p = events
    d = np.asarray(drain_deadlines, np.float64)
    keep = t < d[-1]
    group = np.searchsorted(d, t, side="right")
    # position of each event from the end of its drain group
    ends = np.searchsorted(group, group, side="right")
    keep &= (ends - np.arange(len(t))) <= queue_capacity
    return x[keep], y[keep], t[keep], p[keep]


# ----------------------------------------------------------------------------
# products
# ----------------------------------------------------------------------------

def active_surface(x, y, t32, p, h: int, w: int, polarities: int) -> np.ndarray:
    """Latest write time per (polarity, y, x); -inf where never written."""
    sae = np.full((polarities, h, w), -np.inf, np.float32)
    pol = p if polarities > 1 else np.zeros_like(p)
    np.maximum.at(sae, (pol, y, x), t32)
    return sae


def event_count(x, y, h: int, w: int, n_bits: int) -> np.ndarray:
    """Saturating per-pixel count of events, polarities merged."""
    c = np.bincount(y.astype(np.int64) * w + x, minlength=h * w)
    return np.minimum(c, 2 ** n_bits - 1).astype(np.float32).reshape(h, w)


def fit_transient(transient: dict) -> tuple:
    """``(a1, tau1, a2, tau2, b)`` in float64 from the configuration's
    ``transient``: its ``points`` ``[[t_s, volts], ...]`` and its
    ``fit``: least squares of the double exponential over a grid of
    ``grid`` geometric steps per time constant, ``tau1 <= tau2``, both
    in ``tau_range_s`` at first; each of ``rounds`` rounds solves the
    linear coefficients ``(a1, a2, b)`` at every grid pair, keeps the
    pair of least squared residual so far, and narrows each time
    constant's range to its best value divided and multiplied by
    ``zoom``."""
    t, v = np.asarray(transient["points"], np.float64).T
    fit = transient["fit"]
    lo, hi = fit["tau_range_s"]
    ranges = [(lo, hi), (lo, hi)]
    best_rss, best = math.inf, None
    for _ in range(fit["rounds"]):
        g1 = np.geomspace(*ranges[0], fit["grid"])
        g2 = np.geomspace(*ranges[1], fit["grid"])
        tau1, tau2 = (a.ravel() for a in np.meshgrid(g1, g2, indexing="ij"))
        ok = tau2 >= tau1
        tau1, tau2 = tau1[ok], tau2[ok]
        design = np.stack([np.exp(-t[None] / tau1[:, None]),
                           np.exp(-t[None] / tau2[:, None]),
                           np.ones((len(tau1), len(t)))], axis=-1)
        coef = np.einsum("gij,j->gi", np.linalg.pinv(design), v)
        resid = np.einsum("gij,gj->gi", design, coef) - v
        rss = np.einsum("gi,gi->g", resid, resid)
        i = int(np.argmin(rss))
        if rss[i] < best_rss:
            best_rss, best = rss[i], (tau1[i], tau2[i], coef[i])
        z = fit["zoom"]
        ranges = [(best[0] / z, best[0] * z), (best[1] / z, best[1] * z)]
    tau1, tau2, (a1, a2, b) = best
    return float(a1), float(tau1), float(a2), float(tau2), float(b)


@functools.lru_cache(maxsize=None)
def _fit_json(transient_json: str) -> tuple:
    return fit_transient(json.loads(transient_json))


def transient_constants(config: dict) -> tuple:
    """The fitted constants of ``config``'s cell, computed once per run."""
    return _fit_json(json.dumps(config["transient"], sort_keys=True))


def _transient(c, dt):
    import jax.numpy as jnp

    return (c[0] * jnp.exp(-dt / c[1]) + c[2] * jnp.exp(-dt / c[3]) + c[4])


def decayed(sae, t_now, c):
    """Cell voltages at ``t_now``; ``c`` = (a1, tau1, a2, tau2, b) in the
    working precision.  0 where never written."""
    import jax.numpy as jnp

    dt = t_now - sae.astype(c.dtype)
    return jnp.where(jnp.isfinite(sae), _transient(c, dt),
                     jnp.zeros((), c.dtype))


def stcf_support(v, v_tw, radius: int):
    """Per plane: how many cells of the (2r+1)^2 patch, centre excluded,
    hold a voltage above ``v_tw`` (zero outside the array)."""
    import jax.numpy as jnp

    m = (v > v_tw).astype(jnp.int32)
    h, w = m.shape[-2:]
    mp = jnp.pad(m, [(0, 0)] * (m.ndim - 2) + [(radius, radius)] * 2)
    acc = jnp.zeros_like(m)
    for dy in range(2 * radius + 1):
        for dx in range(2 * radius + 1):
            if dy == radius and dx == radius:
                continue
            acc = acc + mp[..., dy:dy + h, dx:dx + w]
    return acc


# ----------------------------------------------------------------------------
# the GoogLeNet-style head
# ----------------------------------------------------------------------------

def _conv_shapes(cin, cout, k):
    return {"w": (k, k, cin, cout), "b": (cout,)}


def _inception_shapes(cin, c1, c3, c5, cp):
    return {"b1": _conv_shapes(cin, c1, 1),
            "b3a": _conv_shapes(cin, c3 // 2, 1),
            "b3b": _conv_shapes(c3 // 2, c3, 3),
            "b5a": _conv_shapes(cin, c5 // 2, 1),
            "b5b": _conv_shapes(c5 // 2, c5, 5),
            "bp": _conv_shapes(cin, cp, 1)}


def head_shapes(in_channels: int, n_classes: int, width: int) -> dict:
    """Parameter shapes: a 5x5 stride-2 stem, two inception blocks
    (1x1, 1x1-3x3, 1x1-5x5, 3x3-maxpool-1x1 branches) with 3x3 stride-2
    max pools before each, global average pooling, a dense layer."""
    w = width
    return {"stem": _conv_shapes(in_channels, w, 5),
            "inc1": _inception_shapes(w, w // 2, w, w // 4, w // 4),
            "inc2": _inception_shapes(2 * w, w, 2 * w, w // 2, w // 2),
            "head": {"w": (4 * w, n_classes), "b": (n_classes,)}}


def init_head(seed: int, in_channels: int, n_classes: int, width: int):
    """Head weights drawn from ``seed`` on the device in one program:
    He-normal kernels, small normal biases, float32."""
    import jax
    import jax.numpy as jnp

    shapes = head_shapes(in_channels, n_classes, width)
    leaves, tree = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple))

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, s in zip(keys, leaves):
            if len(s) == 1:
                out.append(0.1 * jax.random.normal(k, s, jnp.float32))
            else:
                fan_in = int(np.prod(s[:-1]))
                out.append(jax.random.normal(k, s, jnp.float32)
                           * np.float32(math.sqrt(2.0 / fan_in)))
        return jax.tree_util.tree_unflatten(tree, out)

    word = int(np.random.SeedSequence(int(seed) % 2 ** 63).generate_state(1)[0])
    return make(jax.random.PRNGKey(word))


def head_logits(params, x, dtype):
    """``x``: (B, H, W, C) -> (B, n_classes) float32; float32 at
    precision highest, any narrower type at its native precision."""
    import jax
    import jax.numpy as jnp

    prec = (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else jax.lax.Precision.DEFAULT)
    cast = lambda a: jnp.asarray(a).astype(dtype)

    def conv(p, a, stride=1):
        y = jax.lax.conv_general_dilated(
            a, cast(p["w"]), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)
        return jax.nn.relu(y + cast(p["b"]))

    def pool(a, stride):
        return jax.lax.reduce_window(a, jnp.asarray(-jnp.inf, a.dtype),
                                     jax.lax.max, (1, 3, 3, 1),
                                     (1, stride, stride, 1), "SAME")

    def inception(p, a):
        b1 = conv(p["b1"], a)
        b3 = conv(p["b3b"], conv(p["b3a"], a))
        b5 = conv(p["b5b"], conv(p["b5a"], a))
        bp = conv(p["bp"], pool(a, 1))
        return jnp.concatenate([b1, b3, b5, bp], axis=-1)

    a = conv(params["stem"], cast(x), stride=2)
    a = inception(params["inc1"], pool(a, 2))
    a = inception(params["inc2"], pool(a, 2))
    a = a.mean(axis=(1, 2))
    y = jnp.dot(a, cast(params["head"]["w"]), precision=prec)
    return (y + cast(params["head"]["b"])).astype(jnp.float32)


# ----------------------------------------------------------------------------
# one sensor's products at one deadline
# ----------------------------------------------------------------------------

def _plan(tier: dict, config: dict) -> tuple:
    """The tier's products as a hashable plan for the device program."""
    return tuple((name, p["kind"], p.get("radius", config["stcf_radius"]),
                  tuple(p.get("inputs", ()))) for name, p in
                 tier["products"].items() if p["kind"] != "count")


def _device_products(sae, t_now, consts, head_params, plan, dtype):
    import jax.numpy as jnp

    c = consts[:5].astype(dtype)
    v = decayed(sae, t_now.astype(dtype), c)
    v_tw = _transient(c, consts[5].astype(dtype))
    out = {}
    for name, kind, radius, _ in plan:
        if kind == "surface":
            out[name] = v.astype(jnp.float32)
        elif kind == "stcf":
            out[name] = stcf_support(v, v_tw, radius)
    for name, kind, _, inputs in plan:
        if kind == "classify":
            stack = jnp.concatenate([out[i] for i in inputs], axis=0)
            out[name] = head_logits(head_params,
                                    jnp.moveaxis(stack, 0, -1)[None], dtype)[0]
    return out


_DEVICE = {}


def products(config: dict, tier: dict, held, t_read: float, epoch: float,
             head_params, dtype) -> Dict[str, np.ndarray]:
    """Every product of ``tier`` for one sensor whose held events are
    ``held`` (``(x, y, t64, p)``), read at absolute time ``t_read``.
    The constants enter the device program as arguments, so the
    compiler cannot fold them into the arithmetic."""
    import jax
    import jax.numpy as jnp

    h, w, pol = config["h"], config["w"], config["polarities"]
    x, y, t, p = held
    t32 = (t - epoch).astype(np.float32)
    sae = active_surface(x, y, t32, p, h, w, pol)
    consts = np.array(transient_constants(config) + (config["tau_tw"],),
                      np.float32)
    key = jnp.dtype(dtype).name
    fn = _DEVICE.get(key)
    if fn is None:
        fn = _DEVICE[key] = jax.jit(
            lambda s, tn, c, hp, plan: _device_products(s, tn, c, hp, plan,
                                                        dtype),
            static_argnums=(4,))
    out = {k: np.asarray(v) for k, v in fn(
        sae, np.float32(t_read - epoch), consts, head_params,
        _plan(tier, config)).items()}
    for name, prod in tier["products"].items():
        if prod["kind"] == "count":
            out[name] = event_count(x, y, h, w, prod.get("n_bits", 4))
    return {name: out[name] for name in tier["products"]}


# ----------------------------------------------------------------------------
# comparison
# ----------------------------------------------------------------------------

#: the numbers compared, and how each is folded over samples
NUMBERS = ("surface_ulp", "stcf_mismatch_share", "count_mismatch",
           "logit_gap", "schedule_mismatch")


def ulp_distance(a, b) -> int:
    """Largest float32 ULP distance between two arrays (opposite signs
    count as far apart as the values' distance to zero sum to)."""
    def ordered(v):
        i = np.asarray(v, np.float32).view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)
    if a.size == 0:
        return 0
    return int(np.abs(ordered(a) - ordered(b)).max())


def compare(config: dict, tier: dict, got: Dict[str, np.ndarray],
            want: Dict[str, np.ndarray]) -> Dict[str, float]:
    """The numbers of one sample: the program's products ``got`` against
    ``want``."""
    out: Dict[str, float] = {}
    for name, prod in tier["products"].items():
        a, b = np.asarray(got[name]), np.asarray(want[name])
        if a.shape != b.shape:
            raise ValueError(f"{name}: served shape {a.shape}, "
                             f"reference {b.shape}")
        kind = prod["kind"]
        if kind == "surface":
            out["surface_ulp"] = max(out.get("surface_ulp", 0),
                                     ulp_distance(a, b))
        elif kind == "stcf":
            out["stcf_mismatch_share"] = float((a != b).mean())
        elif kind == "count":
            out["count_mismatch"] = int((a != b).sum())
        elif kind == "classify":
            scale = max(1.0, float(np.abs(b).max()))
            out["logit_gap"] = float(np.abs(a.astype(np.float64) - b).max()
                                     / scale)
    return out


def fold(readings: Sequence[Dict[str, float]]) -> Dict[str, float]:
    """The numbers over samples: the worst sample's, except the STCF
    share, which is over all compared cells (every sample of a cell has
    the same number of cells, so the mean of the shares)."""
    out: Dict[str, float] = {}
    shares = []
    for r in readings:
        for k, v in r.items():
            if k == "stcf_mismatch_share":
                shares.append(v)
            else:
                out[k] = max(out.get(k, v), v)
    if shares:
        out["stcf_mismatch_share"] = float(np.mean(shares))
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """``(correct, lines)``: each number beside its limit; a number with
    no limit, or a NaN, is not correct."""
    ok, lines = True, []
    for k in NUMBERS:
        if k not in numbers:
            continue
        v, lim = numbers[k], limits.get(k)
        good = lim is not None and v == v and v <= lim
        ok &= good
        lines.append((k, v, lim))
    return ok, lines
