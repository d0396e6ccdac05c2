"""base_step_ms (model step, base program): the harness's host clock around
each launched ``step()`` in the window whose config is ``base``, summed,
over the number of such steps."""
from bench import stats


def read(run):
    s = [x for x in run["steps"] if x[stats.CONFIG] == "base"]
    if not s:
        return None
    return 1e3 * sum(x[stats.T1] - x[stats.T0] for x in s) / len(s)
