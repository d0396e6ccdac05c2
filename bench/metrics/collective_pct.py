"""collective_pct (collectives: the Ulysses all-to-all and the TP
all-reduces): each device's collective time (the union of its collective
ops' intervals in the traced slice, ``bench.trace_reduce``) over its busy
time, mean over the cell's devices."""


def read(run):
    tr = run["trace"]
    devs = [d for d in (tr or {}).get("devices", []) if d["busy_s"] > 0]
    if not devs:
        return None
    return 100.0 * sum(d["collective_s"] / d["busy_s"] for d in devs) \
        / len(devs)
