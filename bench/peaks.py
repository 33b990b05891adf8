"""Peak rates of each accelerator, and the operations and bytes that one
deadline's work requires.

``PEAKS`` is keyed by ``device_kind`` as JAX reports it; a device that
is not in the table is an error, never a default.  The counts below
describe the work the algorithm needs for the products the due sensors
asked for, read once and written once, not the work the program happens
to do today: a program that reads only due slots, or decays once for
several products, then reads as a higher share and never passes 100%.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB HBM2 at 819 GB/s per chip
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


#: float32 operations of one decayed cell: one subtraction, two divides,
#: two exponentials, two multiplies, two adds
DECAY_FLOPS_PER_CELL = 9
F32 = 4


def conv_flops(h: int, w: int, k: int, cin: int, cout: int) -> int:
    return 2 * h * w * k * k * cin * cout


def head_flops(h: int, w: int, in_channels: int, n_classes: int,
               width: int) -> int:
    """Multiply-adds of the GoogLeNet-style head (``reference.head_shapes``)
    on one (h, w, in_channels) input, counted as two operations each."""
    c = width
    half = lambda n: -(-n // 2)
    h1, w1 = half(h), half(w)                 # 5x5 stride-2 stem
    f = conv_flops(h1, w1, 5, in_channels, c)
    h2, w2 = half(h1), half(w1)               # max pool, stride 2
    f += _inception_flops(h2, w2, c, c // 2, c, c // 4, c // 4)
    h3, w3 = half(h2), half(w2)
    f += _inception_flops(h3, w3, 2 * c, c, 2 * c, c // 2, c // 2)
    return f + 2 * 4 * c * n_classes


def _inception_flops(h, w, cin, c1, c3, c5, cp) -> int:
    return (conv_flops(h, w, 1, cin, c1) + conv_flops(h, w, 1, cin, c3 // 2)
            + conv_flops(h, w, 3, c3 // 2, c3) + conv_flops(h, w, 1, cin, c5 // 2)
            + conv_flops(h, w, 5, c5 // 2, c5) + conv_flops(h, w, 1, cin, cp))


def product_work(config: dict, tier: dict) -> Dict[str, float]:
    """Operations and bytes one due sensor of ``tier`` requires.

    ``decay_bytes``: the active-event surface read once, and each decayed
    product (surface, STCF support) written once.  ``bytes`` adds the
    counter plane read and the count written; ``flops`` counts one decay
    of the surface, the STCF patch sums, and the head.
    """
    h, w, p = config["h"], config["w"], config["polarities"]
    cells = p * h * w
    kinds = [q["kind"] for q in tier["products"].values()]
    decay_bytes = flops = 0.0
    if any(k in ("surface", "stcf") for k in kinds):
        decay_bytes += cells * F32
        flops += cells * DECAY_FLOPS_PER_CELL
    nbytes = 0.0
    for prod in tier["products"].values():
        kind = prod["kind"]
        if kind in ("surface", "stcf"):
            decay_bytes += cells * F32
        if kind == "stcf":
            r = prod.get("radius", config["stcf_radius"])
            flops += cells * (2 * r + 1) ** 2
        elif kind == "count":
            nbytes += 2 * h * w * F32
        elif kind == "classify":
            flops += head_flops(h, w, len(prod["inputs"]) * p,
                                prod["n_classes"], prod["width"])
    return {"decay_bytes": decay_bytes, "bytes": decay_bytes + nbytes,
            "flops": flops}


def scatter_bytes(n_events: int, with_counts: bool) -> float:
    """Each event read once (x, y, t, p: 16 bytes) and its cell written
    once; with a counter plane the count cell too."""
    return n_events * (16 + F32 + (2 * F32 if with_counts else 0))


def least_time_s(flops: float, nbytes: float, peaks: Dict[str, float]):
    """``(seconds, bound)``: the larger of the compute and the memory
    time, and which of the two it is."""
    tc = flops / peaks["flops_bf16"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
