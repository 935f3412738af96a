"""Real segments per tile-padded device row over the window
(``SchedulerStats.occupancy``: segments / rows_dispatched), in percent."""


def read(ctx):
    occ = ctx.counters.get("occupancy")
    return None if occ is None else 100.0 * occ
