"""The matching work's least time at HBM bandwidth
(``roofline.match_min_seconds`` over the bytes the traced window
answered) as a share of the busiest chip's device busy time."""

from chipbench import roofline


def read(ctx):
    if ctx.trace is None or not ctx.trace.busy_max_s:
        return None
    least = roofline.match_min_seconds(ctx.counters["bytes"],
                                       len(ctx.config["patterns"]),
                                       ctx.chips, ctx.device_kind)
    return 100.0 * least / ctx.trace.busy_max_s
