"""Engine: device-idle ms per window step while the host was inside the
engine's spans (``engine.*``), the EMA update or the metrics' transfer to
the host, averaged over chips.  Each idle instant goes to the innermost
program span open then."""

from benchlib import program_trace

SPANS = ("train.ema_update", "train.metrics")


def read(ctx):
    return program_trace.idle_ms(
        ctx, lambda name: name.startswith("engine.") or name in SPANS)
