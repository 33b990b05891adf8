"""decay_roofline: percent of the decay and STCF kernels' device time
(the Mosaic kernels of ``kernels/ts_decay.py`` and ``kernels/stcf.py``,
the only Pallas kernels of the read programs, ``programs.READ``) that
the decay work the due products need would take at the chip's HBM
bandwidth.  That work is
memory-bound: per due sensor the active-event surface read once and
each decayed product (surface, STCF support) written once
(``peaks.product_work``).  The bytes of every due sensor over one
chip's bandwidth, divided by the kernel time summed over the chips,
is the share of the chips' summed bandwidth for any number of chips:
``n`` chips move the bytes in ``1/n`` of the time each.  Moves
``deadline_p95_ms``."""

import peaks
from programs import READ


def read(ctx):
    if ctx.peaks is None:
        return None
    spent = ctx.trace.op_s(READ, mosaic=True)
    if not spent:
        return None
    tiers = {t["tier"]: t for t in ctx.config["tiers"]}
    nbytes = sum(len(sensors) * peaks.product_work(ctx.config, tiers[tier])["decay_bytes"]
                 for info in ctx.steps for tier, sensors in info.due.items())
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / spent
