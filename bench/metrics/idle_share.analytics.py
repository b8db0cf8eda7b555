"""Share of the traced window in which no operation ran on the device,
in %, for the analytics driver."""


def read(run):
    if run.driver != "analytics" or run.trace is None:
        return None
    t = run.trace
    return (1.0 - t["busy_s"] / t["window_s"]) * 100
