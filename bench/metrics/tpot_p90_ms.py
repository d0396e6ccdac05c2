"""tpot_p90_ms: 90th percentile of the per-request mean token gap inside
the window."""
from bench import stats


def read(run):
    p = stats.pct(stats.tpot_s(run), 90)
    return None if p is None else p * 1e3
