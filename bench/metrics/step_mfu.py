"""Device: the model FLOPs of the window's training steps, at their real
atoms and edges, over the traced window times the chips' peak FLOP/s."""


def read(ctx):
    steps, work = ctx["steps"], ctx["work"]
    if not steps:
        return None
    flops = sum(work.train_step_flops(ctx["sizes"], s["atoms"], s["edges"])
                for s in steps)
    peak = ctx["chips"] * ctx["peaks"]["flops_per_s"]
    return 100.0 * flops / (ctx["trace"].window_s * peak)
