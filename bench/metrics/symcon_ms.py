"""Kernels: device ms per window step in operations under the program's
``symcon`` scope (the registry-resolved symmetric contraction, every pass
and every implementation), averaged over chips."""

from benchlib import program_trace


def read(ctx):
    return program_trace.scope_ms(ctx, "symcon")
