"""Model: device ms per window step in the second-order pass, the
parameter gradient through the forces: operations whose scope carries two
``transpose(`` in its name stack, averaged over chips."""

from benchlib import program_trace


def read(ctx):
    return program_trace.second_order_ms(ctx)
