"""step_mfu: percent of the window's wall time that the deadlines'
required work would take on the chip at its peaks.  Per deadline the
work is the products the due sensors asked for (one decay per sensor,
STCF patch sums, counts, the head's operations; each product written
once) and the scatter of the deadline's events; its least time is the
larger of operations over the bf16 peak and bytes over HBM bandwidth
(``peaks.least_time_s``).  Moves ``deadline_p95_ms``, like the
kernel rooflines it bounds."""

import peaks


def read(ctx):
    if ctx.peaks is None or not ctx.steps or ctx.window_s <= 0:
        return None
    tiers = {t["tier"]: t for t in ctx.config["tiers"]}
    counts = any(p["kind"] == "count" for t in ctx.config["tiers"]
                 for p in t["products"].values())
    total = 0.0
    for info in ctx.steps:
        flops = nbytes = 0.0
        for tier, sensors in info.due.items():
            w = peaks.product_work(ctx.config, tiers[tier])
            flops += len(sensors) * w["flops"]
            nbytes += len(sensors) * w["bytes"]
        nbytes += peaks.scatter_bytes(info.n_events, counts)
        total += peaks.least_time_s(flops, nbytes, ctx.peaks)[0]
    return 100.0 * total / ctx.window_s
