"""device_idle_share.<rate|tail>: share of the traced window in which the
chip ran no operation (device trace): 1 - (union of device op intervals /
window), averaged over the chips used, in %."""


def read(w, name):
    t = w.trace
    if not t or not t.get("devices") or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
