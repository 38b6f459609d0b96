"""Useful denoiser FLOPs delivered over the window, as a share of the
chips' peak: rows of the requests delivered in the window × their own
step count × forwards per step (2 for a guided row, 1 for an
unconditional one) × one forward's FLOPs from ``cost.py``.  Padding and
frozen iterations do not count.  Moves ``images_per_s``."""


def read(rec):
    if rec["kind"] != "backlog":
        return None
    cost, win = rec["cost"], rec["window"]
    fwd = cost.forward_flops(rec["spec"])
    flops = 0.0
    for rid in win["in_window"]:
        r = win["submitted"][rid]
        flops += r.images * r.steps * (1 if r.cond is None else 2) * fwd
    peak = rec["peak"]["flops_per_s"]
    return 100.0 * flops / (win["window_s"] * rec["chips"] * peak)
