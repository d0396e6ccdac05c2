"""idle_pct (device): 1 - the union of device op intervals over the traced
window, mean over the cell's devices."""


def read(run):
    tr = run["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
