"""One general traffic generator, driven by a mix's data file.

A mix file (``bench/traffic/<mix>.json``) holds only parameters. Every
seed gets the same amount of work, in its own order: the multiset of
lengths, of popularity ranks and of inter-arrival gaps is fixed by the file
(stratified: the ``(i + 0.5) / n`` quantiles of each distribution, and
ranks apportioned exactly to their shares), and ``--seed`` draws which
request gets which prompt length, output length, rank, shared prefix and
gap, which expert holds which rank, and every token id. So two seeds differ
in the schedule, not in how much work it holds.

Keys of a mix file:

``loop``           ``"open"`` (arrivals on a schedule) or ``"closed"``
                   (``clients`` each send their next request when the
                   previous one finishes)
``rate_per_s``     open loop: the window of ``seconds`` gets
                   ``round(rate_per_s * seconds)`` requests, the last of
                   them before the window closes
``arrivals``       open loop: ``{"process": "poisson"}`` (exponential gaps,
                   the default) or ``{"process": "gamma", "cv": c}`` (gamma
                   gaps with coefficient of variation ``c``: bursts for
                   ``c > 1``)
``prompt``/``output``  ``{"median", "sigma", "min", "max"}``: lognormal
                   lengths, clipped
``classes``        instead of ``prompt``/``output``: a list of
                   ``{"share", "prompt", "output"}``, length classes mixed
                   in one queue in the given shares
``prefix``         optional ``{"share", "count", "length": {...}}``: that
                   share of requests starts with one of ``count`` shared
                   prefixes (a system prompt) of lognormal length, ahead of
                   its own prompt
``experts``        ``{"zipf_s": s}`` popularity over the config's experts,
                   or ``{}`` for uniform
``n_requests``     closed loop: requests per client; each client's
                   ``j``-th requests together take one of each of
                   ``clients`` strata of output (and, with one class,
                   prompt) length, whatever the seed
``warm_s``         closed loop only: seconds of traffic before the window
``drain_cap_s``    how long in-flight requests may finish after the window
``sample_tokens``  served tokens the output check samples at least
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np
from scipy import special, stats


@dataclass
class Item:
    prompt: np.ndarray          # (S,) int32 token ids
    max_new_tokens: int
    expert: str


@dataclass
class Traffic:
    loop: str
    items: List[Item]                   # open: in arrival order
    offsets_s: Optional[np.ndarray]     # open: scheduled arrival offsets
    clients: List[List[Item]]           # closed: each client's requests
    popularity: List[str]               # expert names, most popular first
    params: dict


def _q(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """The ``n`` stratified lengths of a lognormal with the given median
    and sigma, rounded and clipped to ``[min, max]``, shortest first."""
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * special.ndtri(_q(n)))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def apportion(n: int, shares) -> np.ndarray:
    """``n`` split in proportion to ``shares`` by largest remainder."""
    w = np.asarray(shares, np.float64)
    exact = n * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    left = n - out.sum()
    out[np.argsort(-(exact - out), kind="stable")[:left]] += 1
    return out


def zipf_shares(n: int, s: float) -> np.ndarray:
    """Share of requests of each popularity rank ``1..n`` under Zipf(s)."""
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def gaps(spec: dict, n: int, seconds: float) -> np.ndarray:
    """The ``n`` stratified inter-arrival gaps of the arrival process,
    scaled so that the last of ``n`` arrivals lands at
    ``seconds * n / (n + 1)``."""
    proc = spec.get("process", "poisson")
    if proc == "poisson":
        g = -np.log1p(-_q(n))
    elif proc == "gamma":
        k = 1.0 / spec["cv"] ** 2
        g = stats.gamma.ppf(_q(n), k)
    else:
        raise ValueError(f"unknown arrival process {proc!r}")
    return g * (seconds * n / (n + 1) / g.sum())


def _rng(seed: int) -> np.random.RandomState:
    return np.random.RandomState(np.random.SeedSequence(
        [seed % 2**32, seed // 2**32]).generate_state(4))


def classes(params: dict) -> List[dict]:
    if "classes" in params:
        return params["classes"]
    return [{"share": 1.0, "prompt": params["prompt"],
             "output": params["output"]}]


def _requests(params: dict, n: int, rs: np.random.RandomState):
    """Prompt and output lengths of ``n`` requests, each multiset fixed by
    the mix, paired and ordered by ``rs``."""
    cls = classes(params)
    plen, olen = [], []
    for c, k in zip(cls, apportion(n, [c["share"] for c in cls])):
        plen.append(lengths(c["prompt"], int(k)))
        olen.append(rs.permutation(lengths(c["output"], int(k))))
    order = rs.permutation(n)
    plen = np.concatenate(plen)[order]
    olen = np.concatenate(olen)[order]
    return plen, olen


def _by_depth(key, clients: int, rs) -> np.ndarray:
    """An order of the ``clients * k`` requests, depth first (the ``j``-th
    request of every client, then the next), under which every depth holds
    one request from each of ``clients`` strata of ``key``: however many
    depths a closed-loop window consumes, its work is nearly the same for
    every seed."""
    k = len(key) // clients
    pick = np.stack([rs.permutation(k) for _ in range(clients)])   # (C, k)
    strata = np.argsort(key, kind="stable").reshape(clients, k)
    order = strata[np.arange(clients)[:, None], pick].T            # (k, C)
    order = np.stack([rs.permutation(row) for row in order])
    return order.reshape(-1)


def _ranks(params: dict, n_experts: int, n: int, rs) -> np.ndarray:
    s = params.get("experts", {}).get("zipf_s")
    p = zipf_shares(n_experts, s) if s else np.full(n_experts, 1 / n_experts)
    return rs.permutation(np.repeat(np.arange(n_experts),
                                    apportion(n, p)))


def _prefixes(params: dict, n: int, vocab_size: int, rs) -> List:
    """Per request, the shared prefix it starts with (an empty array for
    requests with none)."""
    spec = params.get("prefix")
    none = np.zeros((0,), np.int32)
    if not spec:
        return [none] * n
    k = spec["count"]
    pool = [rs.randint(0, vocab_size, int(x)).astype(np.int32)
            for x in rs.permutation(lengths(spec["length"], k))]
    m = int(apportion(n, [spec["share"], 1 - spec["share"]])[0])
    which = np.full(n, -1)
    which[rs.permutation(n)[:m]] = np.arange(m) % k
    return [pool[j] if j >= 0 else none for j in which]


def generate(params: dict, seed: int, expert_names: List[str],
             vocab_size: int, seconds: float) -> Traffic:
    """The run's requests for ``seed`` (any non-negative integer) and a
    window of ``seconds``."""
    n_exp = len(expert_names)
    loop = params["loop"]
    if loop == "open":
        n = max(1, int(round(params["rate_per_s"] * seconds)))
    elif loop == "closed":
        n = params["n_requests"] * params["clients"]
    else:
        raise ValueError(f"unknown loop {loop!r}")
    rs = _rng(seed)
    popularity = [expert_names[i] for i in rs.permutation(n_exp)]
    plen, olen = _requests(params, n, rs)
    if loop == "closed":
        c = params["clients"]
        order = _by_depth(olen, c, rs)
        plen, olen = plen[order], olen[order]
        if "classes" not in params:
            # one class: prompts stratified by depth too, paired anew
            plen = plen[_by_depth(plen, c, rs)]
    rank = _ranks(params, n_exp, n, rs)
    pre = _prefixes(params, n, vocab_size, rs)
    items = [Item(np.concatenate([
        pre[i], rs.randint(0, vocab_size, int(plen[i])).astype(np.int32)]),
        int(olen[i]), popularity[rank[i]]) for i in range(n)]
    if loop == "open":
        g = rs.permutation(gaps(params.get("arrivals", {}), n, seconds))
        return Traffic(loop, items, np.cumsum(g), [], popularity, params)
    c = params["clients"]
    clients = [items[i::c] for i in range(c)]
    return Traffic(loop, [], None, clients, popularity, params)
