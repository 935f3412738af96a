"""Real symbols over the row-steps the scan loops ran, in percent, summed
over the calls of the traced window (the ``real_symbols`` and
``run_symbols`` the matcher records on each call's ``repro.finish`` span;
``program_spans.scan_fill_pct``).  Nothing where the program records no
such span."""

from chipbench import program_spans


def read(ctx):
    trace = program_spans.for_readings(ctx)
    return None if trace is None else program_spans.scan_fill_pct(trace)
