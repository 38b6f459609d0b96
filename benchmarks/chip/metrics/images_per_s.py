"""Images of the requests delivered in the window over the window, which
runs from the drain's start to the first wave retirement at or after
``--seconds``: all the work and all the time of the window."""


def read(rec):
    win = rec["window"]
    if rec["kind"] != "backlog" or win["window_s"] <= 0:
        return None
    return win["images"] / win["window_s"]
