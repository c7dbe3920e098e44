"""device_idle_share: share of the traced window in which no operation ran
on the device, in percent (1 - busy / window)."""


def read(run):
    t = run.trace
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
