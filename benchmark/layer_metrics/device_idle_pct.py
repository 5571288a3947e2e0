"""100 x (1 - device-busy seconds / traced seconds), from the trace."""


def read(run):
    t = run.trace
    if not t or not t["window_s"] or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
