"""Share of the traced window in which no operation ran on the chip,
mean over the chips a round-drain cell uses (1 − the union of the XLA op
intervals / the window; per chip on standard error).  Moves
``images_per_s``."""


def read(rec):
    if rec["kind"] != "backlog" or rec["trace"] is None:
        return None
    return 100.0 * rec["trace"]["idle_share"]
