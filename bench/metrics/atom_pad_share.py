"""Data distribution: the share of the window's atom slots that hold no
real atom (1 - real atoms / atom slots over the bins trained): the padding
Algorithm 1 leaves in each bin."""


def read(ctx):
    steps = ctx["steps"]
    if not steps:
        return None
    slots = len(steps) * ctx["slots"][0]
    return 100.0 * (1.0 - sum(s["atoms"] for s in steps) / slots)
