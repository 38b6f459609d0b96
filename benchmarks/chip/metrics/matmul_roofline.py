"""Share of its roofline that the denoiser's dense matmuls reach in a
round drain: the least time the chip could take for the weight matmuls
of the waves that retired in the traced window (``cost.py``, each
product bounded by its FLOPs or by its operands and result), over the
device time the trace gives those matmuls: the convolution fusions (how
XLA runs a dot on the TPU) that neither read nor write the S×S attention
scores.  Moves ``images_per_s``."""


def read(rec):
    red, win = rec["trace"], rec["window"]
    if rec["kind"] != "backlog" or red is None:
        return None
    c = win["counters"]
    if c.get("waves", 0) < 2:
        return None
    cost, spec = rec["cost"], rec["spec"]
    # waves dispatched by the window's close, less the one still in flight
    retired = c["waves"] - 1
    calls = retired * c["row_iters_scheduled"] / c["waves"] / win["wave_rows"]
    ideal = calls * cost.dense_matmul_roofline_s(spec, 2 * win["wave_rows"],
                                                 rec["peak"])
    S = cost.seq_len(spec)
    scores = f"{S},{S}]"
    tracer = rec["tracereduce"]
    spent = sum(t for text, t in red["by_op_s"].items()
                if tracer.category(text) == "convolution fusion"
                and scores not in text)
    return 100.0 * ideal / spent if spent > 0 else None
