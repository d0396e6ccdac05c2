"""What decides ``correct``: served tokens against the float32 reference.

After the window, a sample drawn from the seed of the requests the run
finished (the longest among them, then others in seed order until
``tokens`` served tokens are covered; requests still in flight when too few
finished) is run once through the reference over its prompt and served
tokens. At every served position the gap is the reference's best logit
less its logit of the served token; the run compares the mean gap over the
served tokens with the cell's limit (``bench/cells/<cell>.json``) and
prints the widest for the record (the widest swings from seed to seed by
nature and did not separate bf16 runs from the control by 3x). Greedy
tokens only: the served path samples greedily.

On a mesh the sample also takes, where the run has them, a request with
tokens from each program (base and shift), so both are checked."""
from __future__ import annotations

import numpy as np

from bench import workload as WL


def sample(tracked, seed, tokens, max_requests):
    done = [t for t in tracked if t.req.finish_reason is not None
            and str(t.req.finish_reason) == "ok" and t.req.generated]
    busy = [t for t in tracked if t.req.finish_reason is None
            and t.req.generated]
    rng = WL._rng(seed, 7)
    pool = sorted(done, key=lambda t: t.req.rid)
    if len(pool) < 2:
        pool += sorted(busy, key=lambda t: t.req.rid)
    if not pool:
        return []
    longest = max(pool, key=lambda t: (len(t.req.generated), -t.req.rid))
    order = [pool[i] for i in rng.permutation(len(pool))]
    chosen = [longest]
    for cfg in ("base", "shift"):
        if not any(t.by_config[cfg] for t in chosen):
            c = next((t for t in order if t.by_config[cfg]), None)
            if c is not None:
                chosen.append(c)
    for t in order:
        if sum(len(c.req.generated) for c in chosen) >= tokens \
                or len(chosen) >= max_requests:
            break
        if t not in chosen:
            chosen.append(t)
    return chosen


def served(chosen):
    """(sequence, served count) pairs: prompt + generated tokens."""
    return ([list(t.req.prompt) + list(t.req.generated) for t in chosen],
            [len(t.req.generated) for t in chosen])


def gaps(spec, seed, chosen, control=False):
    """The reference's gap at every served token of the chosen requests,
    as one array (with ``control``, also the gaps of the tokens that the
    control precision puts first at the same positions)."""
    from bench.reference.dense_gqa import Reference
    seqs, ns = served(chosen)
    out = Reference(spec, seed).served_gaps(seqs, ns, control=control)
    if control:
        return (np.concatenate([g for g, _ in out]),
                np.concatenate([c for _, c in out]))
    return np.concatenate(out)
