"""Kernels: device ms per window step in operations under the program's
``interaction`` scope (the registry-resolved interaction op, every pass and
every implementation: Pallas kernels and the XLA ops of its derivatives),
averaged over chips."""

from benchlib import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "interaction")
