"""rows_per_step (scheduler, engine._run_mixed): mean rows of the launched
steps in the window (the engine's step records, ``n_rows``)."""
from bench import stats


def read(run):
    s = run["steps"]
    return sum(x[stats.ROWS] for x in s) / len(s) if s else None
