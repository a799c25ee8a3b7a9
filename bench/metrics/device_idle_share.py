"""Device: the share of the traced window in which no operation ran on the
chip (1 - union of operation intervals / window), averaged over chips."""

from benchlib import trace


def read(ctx):
    t = ctx["trace"]
    if not t.device_ops:
        return None
    return 100.0 * trace.idle_share(t)
