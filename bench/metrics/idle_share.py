"""Share of the traced slice in which no operation ran on the device
(device, TPU v5e): 1 - busy / window from the profiler trace."""
UNIT = "%"


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100 * (1 - run.trace["busy_s"] / run.trace["window_s"])
