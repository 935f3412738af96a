"""Share of the traced window in which the device idled while the host was
inside one of the matcher's root spans (``repro.membership_batch`` and the
other public calls), mean over chips (``program_spans.host_stall_pct``).
The rest of the idle share is the caller's.  Nothing where the program
records no root span."""

from chipbench import program_spans


def read(ctx):
    trace = program_spans.for_readings(ctx)
    return None if trace is None else program_spans.host_stall_pct(trace)
