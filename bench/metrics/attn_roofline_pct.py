"""attn_roofline_pct (attention kernel, kernels/paged_ragged_attention.py):
the least time the chip needs for the traced steps' attention (the larger of
bytes over HBM bandwidth and operations over peak, per device) over the
Pallas kernel's device time in the trace. Memory bounds it for decode."""
from bench import stats


def read(run):
    tr = run["trace"]
    steps = run["trace_steps"]
    if not tr or not steps or tr["kernel_s"] <= 0:
        return None
    g = run["groups"]
    t_mem = stats.attention_bytes(run["spec"], steps) / g \
        / run["peak"]["hbm_bytes_per_s"]
    t_flop = stats.attention_flops(run["spec"], steps) / g \
        / run["peak"]["flops"]
    return 100.0 * max(t_mem, t_flop) / tr["kernel_s"]
