"""Host input: host ms per window step in which the training loop waited
for its batch (the program's ``train.wait_batch`` span)."""

from benchlib import program_trace


def read(ctx):
    return program_trace.wait_ms(ctx)
