"""Share of each chip's device busy time spent in all-gather operations
(the chunk-axis exchange of lane states), averaged over chips; nothing
when no all-gather ran."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.share_pct(r"all-gather")
