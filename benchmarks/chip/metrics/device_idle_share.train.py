"""device_idle_share.train: 1 - busy / window from the profiler's trace of the
window: busy is the union of the device's operation intervals."""


def read(run):
    if run.kind != "train" or not run.trace or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
