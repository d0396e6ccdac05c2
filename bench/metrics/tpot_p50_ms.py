"""tpot_p50_ms: median over requests of each request's mean gap between
its tokens emitted inside the window (two or more tokens)."""
from bench import stats


def read(run):
    p = stats.pct(stats.tpot_s(run), 50)
    return None if p is None else p * 1e3
