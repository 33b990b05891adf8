"""decay_roofline: percent of the decay and STCF kernels' device time
(the Mosaic kernels of ``kernels/ts_decay.py`` and ``kernels/stcf.py``,
the only Pallas kernels of the read programs) that the decay work the
due products need would take at the chip's HBM bandwidth.  That work is
memory-bound: per due sensor the active-event surface read once and
each decayed product (surface, STCF support) written once
(``peaks.product_work``).  Moves ``deadline_p95_ms``."""

import peaks

#: XLA program names of the read path, whose Pallas kernels are counted
PROGRAMS = [r"read_spec_products", r"read_head_products"]


def read(ctx):
    if ctx.peaks is None:
        return None
    spent = ctx.trace.op_s(PROGRAMS, mosaic=True)
    if not spent:
        return None
    tiers = {t["tier"]: t for t in ctx.config["tiers"]}
    nbytes = sum(len(sensors) * peaks.product_work(ctx.config, tiers[tier])["decay_bytes"]
                 for info in ctx.steps for tier, sensors in info.due.items())
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / spent
