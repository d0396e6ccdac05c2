"""step_ms (model step, models/model.py forward_fn): the harness's host
clock around each launched ``step()`` in the window, summed, over the
number of launched steps."""
from bench import stats


def read(run):
    s = run["steps"]
    if not s:
        return None
    return 1e3 * sum(x[stats.T1] - x[stats.T0] for x in s) / len(s)
