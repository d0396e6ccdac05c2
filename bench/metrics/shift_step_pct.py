"""shift_step_pct (shift policy, core/policy.py via engine._choose): the
share of the window's launched steps whose config is ``shift`` (the step
records' ``config``)."""
from bench import stats


def read(run):
    s = run["steps"]
    if not s:
        return None
    return 100.0 * sum(x[stats.CONFIG] == "shift" for x in s) / len(s)
