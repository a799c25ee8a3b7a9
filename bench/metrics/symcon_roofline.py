"""Kernels: the symmetric-contraction kernels' share of their roofline:
the summed least times of the ``symcon_fwd`` and ``symcon_bwd`` calls in
the traced window over their summed device time.  Each call's least time
is the larger of its operations over the peak FLOP/s and its bytes over the
peak bandwidth, at the real atoms of the step it belongs to; a step's calls
are counted as the window's calls spread evenly over its steps."""

from benchlib import trace

KERNELS = {"symcon_fwd": False, "symcon_bwd": True}


def read(ctx):
    t, steps, work = ctx["trace"], ctx["steps"], ctx["work"]
    counts = trace.kernel_counts(t, KERNELS)
    times = trace.kernel_time_s(t, KERNELS)
    if not steps or not sum(counts.values()):
        return None
    least = 0.0
    for name, backward in KERNELS.items():
        per_call = [work.least_time(*work.symcon_call(
            ctx["sizes"], s["atoms"], backward), ctx["peaks"])[0]
            for s in steps]
        least += counts[name] * sum(per_call) / len(per_call)
    return 100.0 * least / sum(times.values())
