"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the timed path
served in the window (``sample``) is run through the plain reference of
the configuration's architecture module (``arch/``, built from
``reference.py``) on weights regenerated from the seed and mapped by
that module's ``reference_weights``.  For every served token the gap by
which the reference's logit of that token lies below the reference's
best logit is read; the widest gap is compared with the configuration's
limit.  Served tokens are greedy, so a sound run serves tokens the
reference ranks at or near the top.
"""

from __future__ import annotations

import numpy as np

from bench import reference

SAMPLE_REQUESTS = 4


def sample(finished: list, in_flight: list, seed: int,
           n: int = SAMPLE_REQUESTS) -> list:
    """``n`` requests served in the window: the one with the most served
    tokens, then finished requests drawn from the seed, then -- where
    fewer finished -- requests still in flight at the close, whose
    streamed tokens are as final as a finished request's."""
    pool = [r for r in finished + in_flight if r.out]
    if not pool:
        return []
    longest = max(pool, key=lambda r: len(r.out))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) % 2**64,
                                                        7]))
    rest = [[r for r in group if r is not longest and r.out]
            for group in (finished, in_flight)]
    order = [g[i] for g in rest for i in rng.permutation(len(g))]
    return [longest] + order[:n - 1]


def widest_gap(arch, weights: dict, cfg: dict, requests: list, t_max: int,
               m_max: int) -> tuple:
    """(widest gap over every served token of ``requests``, tokens
    compared) under the reference of ``arch``."""
    gaps = [reference.served_gaps(arch, weights, cfg, np.asarray(r.prompt),
                                  np.asarray(r.out, np.int32), t_max, m_max)
            for r in requests]
    if not gaps:
        return float("nan"), 0
    allg = np.concatenate(gaps)
    return float(allg.max()), int(allg.size)
