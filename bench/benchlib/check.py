"""The output check that decides ``correct``.

Once the window has closed and the program's device state is freed, a
sample of the finished requests, drawn from the seed and always holding the
one with the most served tokens, is run through the configuration's plain
reference: once over each prompt with its served tokens, with the weights
of the expert the request was routed to, as the benchmark made them. At
each served position the reading is how far the served token's reference
logit lies below the reference's best, in units of that position's logit
standard deviation. The number compared is the widest such gap; its limit
is the configuration's ``check.gap_limit``.

So the check covers what made each served token: packed prefill (the first
token), the fused decode step's kernels over the paged pool and its block
tables (every later one), and the weight tier (the expert's weights after
the window's switches and evictions).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import numpy as np


def sample(done: List, seed: int, min_tokens: int) -> List:
    """Requests to check: the longest served, then others in an order drawn
    from ``seed`` until ``min_tokens`` served tokens are covered."""
    if not done:
        return []
    rs = np.random.RandomState(np.random.SeedSequence(
        [seed % 2**32, seed // 2**32, 7]).generate_state(4))
    longest = max(done, key=lambda r: (len(r.output), len(r.tokens), -r.rid))
    rest = [done[i] for i in rs.permutation(len(done))
            if done[i] is not longest]
    out, n = [longest], len(longest.output)
    for r in rest:
        if n >= min_tokens:
            break
        out.append(r)
        n += len(r.output)
    return out


def by_expert(reqs: List) -> Dict[str, List]:
    out: Dict[str, List] = {}
    for r in reqs:
        out.setdefault(r.expert, []).append(r)
    return out


def widest_gaps(m: dict, ref, hosts: Dict[str, dict], reqs: List,
                control: bool = False) -> Dict[str, float]:
    """Per request id, the widest gap of its served tokens (or, with
    ``control``, of the tokens the fp8 control would serve at the same
    positions). Each expert's weights go to the device once."""
    pad = m["deployment"]["max_len"]
    out: Dict[str, float] = {}
    for expert, rs in by_expert(reqs).items():
        params = jax.device_put(hosts[expert])
        for r in rs:
            toks = np.concatenate([r.tokens, r.output[:-1]]).astype(np.int32)
            start = len(r.tokens) - 1
            if control:
                g = ref.control_gaps(m, params, toks, start, len(r.output),
                                     pad)
            else:
                g = ref.gaps(m, params, toks, start, np.asarray(r.output),
                             pad)
            out[r.rid] = float(np.max(g))
        del params
    return out
