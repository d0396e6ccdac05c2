"""mfu_pct (model step): the operations the model needs for the window's
steps (``bench.stats.model_flops``) over window seconds x chips x the
chip's bf16 peak (``bench/peaks.json``)."""
from bench import stats


def read(run):
    if not run["steps"]:
        return None
    f = stats.model_flops(run["spec"], run["steps"])
    return 100.0 * f / (run["seconds"] * run["chips"] * run["peak"]["flops"])
