"""Kernels: the interaction kernels' share of their roofline: the summed
least times of the ``interaction_fwd`` and ``interaction_bwd`` calls in the
traced window over their summed device time.  Each call's least time is the
larger of its operations over the peak FLOP/s and its bytes over the peak
bandwidth, at the real atoms and edges of the step it belongs to; the calls
are counted as spread evenly over the window's steps and the layers."""

from benchlib import trace

KERNELS = {"interaction_fwd": False, "interaction_bwd": True}


def read(ctx):
    t, steps, work, s = ctx["trace"], ctx["steps"], ctx["work"], ctx["sizes"]
    counts = trace.kernel_counts(t, KERNELS)
    times = trace.kernel_time_s(t, KERNELS)
    if not steps or not sum(counts.values()):
        return None
    least = 0.0
    for name, backward in KERNELS.items():
        per_call = [work.least_time(*work.interaction_call(
            s, layer, st["atoms"], st["edges"], backward), ctx["peaks"])[0]
            for st in steps for layer in range(s.n_interactions)]
        least += counts[name] * sum(per_call) / len(per_call)
    return 100.0 * least / sum(times.values())
