"""output_tokens_per_s: every token delivered inside the window (the step
running at its close pro rata), over the window's seconds."""
from bench import stats


def read(run):
    return stats.window_tokens(run) / run["seconds"]
