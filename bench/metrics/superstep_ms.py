"""Device busy time in the traced window per superstep, in ms."""


def read(run):
    if run.driver != "analytics" or run.trace is None:
        return None
    iters = sum(r["iters"] for r in run.records["runs"])
    if not iters:
        return None
    return run.trace["busy_s"] / iters * 1e3
