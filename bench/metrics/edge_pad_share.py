"""Data distribution: the share of the window's edge slots that carry no
real edge (1 - real edges / edge slots over the bins trained)."""


def read(ctx):
    steps = ctx["steps"]
    if not steps:
        return None
    slots = len(steps) * ctx["slots"][1]
    return 100.0 * (1.0 - sum(s["edges"] for s in steps) / slots)
