#!/usr/bin/env python3
"""Smoke test of the CoE serving path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # only the node phase, on four chips

One chip: granite-8b at its published widths with depth cut to 4 layers.
Four experts sit in the host store and the HBM tier holds two, so the
traffic forces prefetch-served switches and evictions. ``ServingEngine``
(8 slots, paged KV, packed prefill, ``backend="fused"``) serves 16 requests
after ``warmup()``. ``--chips 4`` runs ``RDUNode`` on the four chips as
shape 4x1 and as 2x2 over the same requests, and no other phase. Both
compare the logits of the engine's compiled paths, for one request per
expert, with the model's float32 forward on the same weights
(``repro.models.reference``).

Earlier lines report what was measured. The last line of stdout is one JSON
object ``{"ok": true, "device": {...}}``. Without a TPU, or when any check
fails, the script exits non-zero and prints no such line. It runs in one
process and starts none.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

ARCH = "granite-8b"
N_LAYERS = 4
N_EXPERTS = 4
HBM_EXPERTS = 2          # HBM weight tier, in experts
N_SLOTS = 8
BLOCK = 16
MAX_LEN = 2048
N_REQUESTS = 16
PROMPT_LENS = (128, 1024)
NEW_TOKENS = 64
N_DECODE_CHECK = 4       # decode steps compared after the prefill


class SmokeFailure(Exception):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def device_summary() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def smoke_config(cfg=None, n_layers: int = N_LAYERS):
    """``cfg`` (default granite-8b, published widths) with its depth cut to
    ``n_layers``. Returns ``(cfg, cuts)``, one line per cut."""
    from repro.configs import get_config
    cfg = cfg or get_config(ARCH)
    cuts = []
    if cfg.n_layers > n_layers:
        cuts.append(f"n_layers {cfg.n_layers} -> {n_layers}")
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    return cfg, cuts


def make_requests(cfg, expert_names, n=N_REQUESTS, prompt_lens=PROMPT_LENS,
                  new_tokens=NEW_TOKENS, seed=0):
    """``n`` caller-tagged requests, round-robin over the experts, prompts
    drawn from ``prompt_lens`` (inclusive) with ``seed``."""
    from repro.serving import Request
    rs = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        S = rs.randint(prompt_lens[0], prompt_lens[1] + 1)
        toks = rs.randint(0, cfg.vocab_size, (S,)).astype(np.int32)
        reqs.append(Request(rid=i, tokens=toks, max_new_tokens=new_tokens,
                            expert=expert_names[i % len(expert_names)]))
    return reqs


def serve(target, reqs) -> dict:
    """Submit every request to an engine or node, drain, and count."""
    t0 = time.perf_counter()
    for r in reqs:
        target.submit(r)
    done = target.drain()
    return {"requests": len(done), "submitted": len(reqs),
            "tokens": int(sum(len(r.output) for r in done)),
            "seconds": time.perf_counter() - t0}


def memory_plan(engine) -> dict:
    """Device bytes the warmed engine needs while serving: the HBM weight
    tier at capacity, the paged KV pool, and the larger of the compiled
    decode step's and the largest prefill bucket's temporaries. Also
    whether the compiled decode step holds a Mosaic kernel."""
    decode = engine.runner.compiled_extend((engine.n_slots, 1))
    pr = engine.prefill_runner
    prefill = pr.compiled_forward(pr.buckets[-1])
    temps = [c.memory_analysis().temp_size_in_bytes for c in (decode,
                                                              prefill)]
    plan = {"weights": engine.coe.cache.capacity,
            "kv_pool": engine.pool.capacity_bytes(),
            "step_temps": max(temps)}
    plan["total"] = sum(plan.values())
    plan["decode_has_kernel"] = "tpu_custom_call" in decode.as_text()
    return plan


def check_logits(engine, cfg, prompts_by_expert, n_decode=N_DECODE_CHECK,
                 tol=None, emit=print) -> float:
    """For each ``expert -> prompt``, compare the engine's prefill and
    ``n_decode`` decode-step logits with the float32 reference on the same
    weights. Returns the largest error (x reference RMS); raises past
    ``tol`` (default ``BF16_TOL``)."""
    from repro.models.reference import (BF16_TOL, assert_logits_close,
                                        reference_logits)
    from repro.serving.probe import probe_logits
    tol = BF16_TOL if tol is None else tol
    worst = 0.0
    for name, prompt in prompts_by_expert.items():
        params = engine.coe.cache.activate(name)
        got, fed = probe_logits(engine, params, prompt, n_decode)
        ref = reference_logits(cfg, params,
                               np.concatenate([prompt, fed]))[len(prompt) - 1:]
        err = assert_logits_close(got, ref, tol, f"expert {name}")
        emit(f"logits {name}: prefill of {len(prompt)} tokens + {n_decode} "
             f"decode steps vs float32 reference: max error {err:.4g} x RMS "
             f"(tolerance {tol})")
        worst = max(worst, err)
    return worst


def first_prompts(reqs) -> dict:
    """One prompt per expert: each expert's first request."""
    out = {}
    for r in reqs:
        out.setdefault(r.expert, np.asarray(r.tokens, np.int32))
    return out


def run_one_chip(seed: int) -> None:
    import jax
    from repro.launch.serve import build_coe
    from repro.serving import ServingEngine

    dev = jax.devices()[0]
    cfg, cuts = smoke_config()
    print(f"config: {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads} head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} layers={cfg.n_layers}")
    for c in cuts:
        print(f"cut: {c}")

    t0 = time.perf_counter()
    coe, nbytes = build_coe(cfg, N_EXPERTS, HBM_EXPERTS, seed=seed)
    print(f"experts: {N_EXPERTS} x {nbytes} bytes in the host store, HBM "
          f"tier {coe.cache.capacity} bytes ({HBM_EXPERTS} experts); built "
          f"in {time.perf_counter() - t0:.1f}s")
    engine = ServingEngine(coe, cfg, max_len=MAX_LEN, n_slots=N_SLOTS,
                           block_size=BLOCK, backend="fused")
    print(f"engine: backend={engine.runner.backend_name} slots={N_SLOTS} "
          f"block={BLOCK} max_len={MAX_LEN} prefill={engine.prefill_mode} "
          f"buckets={list(engine.prefill_runner.buckets)}")

    t0 = time.perf_counter()
    engine.warmup()
    print(f"compile: warmup took {time.perf_counter() - t0:.1f}s")

    limit = dev.memory_stats()["bytes_limit"]
    plan = memory_plan(engine)
    print(f"memory plan: weights {plan['weights']} + kv_pool "
          f"{plan['kv_pool']} + step temporaries {plan['step_temps']} = "
          f"{plan['total']} bytes; bytes_limit {limit}")
    check(plan["total"] < limit, "memory plan exceeds the device limit")
    print(f"decode step: tpu_custom_call "
          f"{'present' if plan['decode_has_kernel'] else 'ABSENT'}")
    check(plan["decode_has_kernel"], "compiled decode step has no kernel")

    reqs = make_requests(cfg, coe.expert_names(), seed=seed)
    res = serve(engine, reqs)
    st, cs = engine.stats, coe.cache.stats
    print(f"served: {res['requests']}/{res['submitted']} requests, "
          f"{res['tokens']} tokens in {res['seconds']:.2f}s (host clock, "
          f"first traffic after warmup)")
    print(f"switching: switches={st.switches} evictions={cs.evictions} "
          f"prefetch_hits={cs.prefetch_hits} "
          f"prefetch_failures={cs.prefetch_failures} misses={cs.misses}")
    check(res["requests"] == res["submitted"], "not every request served")
    check(res["tokens"] == N_REQUESTS * NEW_TOKENS, "token count short")
    check(st.switches >= 1 and cs.evictions >= 1,
          "traffic forced no switch or no eviction")
    check(cs.prefetch_failures == 0, "a prefetch load failed")
    check(engine.pool.stats.blocks_in_use == 0, "KV blocks leaked")

    worst = check_logits(engine, cfg, first_prompts(reqs))
    print(f"logits: max error {worst:.4g} x RMS over {N_EXPERTS} experts")

    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"device memory: peak_bytes_in_use {peak} of bytes_limit {limit}")
    check(peak < limit, "peak device memory reached the limit")
    coe.cache.close()


def run_node(seed: int) -> None:
    """``RDUNode`` over the four chips as 4x1, then 2x2: same requests,
    same reference, per-group placement read from the arrays."""
    import jax
    from repro.configs import pad_for_tp
    from repro.core import HashRouter
    from repro.launch.serve import build_experts
    from repro.node import RDUNode, make_node_topology

    base, cuts = smoke_config()
    for c in cuts:
        print(f"cut: {c}")
    t0 = time.perf_counter()
    hosts, nbytes = build_experts(base, N_EXPERTS, seed)
    print(f"experts: {N_EXPERTS} x {nbytes} bytes built in "
          f"{time.perf_counter() - t0:.1f}s")
    names = [n for n, _, _ in hosts]
    for shape in ("4x1", "2x2"):
        tp, n_groups = (int(x) for x in shape.split("x"))
        cfg = pad_for_tp(base, tp)
        node = RDUNode(make_node_topology(tp, n_groups), cfg,
                       HashRouter(N_EXPERTS), None,
                       group_hbm_bytes=HBM_EXPERTS * nbytes,
                       n_slots=N_SLOTS, block_size=BLOCK, max_len=MAX_LEN,
                       backend="fused")
        for name, host, domain in hosts:
            node.register_expert(name, host, domain=domain)
        t0 = time.perf_counter()
        node.warmup()
        print(f"[{shape}] compile: warmup took {time.perf_counter() - t0:.1f}s")
        reqs = make_requests(cfg, names, seed=seed)
        res = serve(node, reqs)
        st = node.stats()
        print(f"[{shape}] served: {res['requests']}/{res['submitted']} "
              f"requests, {res['tokens']} tokens in {res['seconds']:.2f}s; "
              f"switches={sum(g['switches'] for g in st.per_group)}")
        check(res["requests"] == res["submitted"], "not every request served")
        seen = set()
        for gs in node.groups:
            devs = set(gs.group.devices)
            check(not devs & seen, "socket groups share a device")
            seen |= devs
            pool_devs = gs.engine.pool.k.sharding.device_set
            name = (gs.coe.cache.expert_ids() or names)[-1]
            params = gs.coe.cache.activate(name)
            param_devs = set().union(*(x.sharding.device_set
                                       for x in jax.tree.leaves(params)))
            print(f"[{shape}] group {gs.group.gid}: devices "
                  f"{sorted(d.id for d in devs)}, kv pool on "
                  f"{sorted(d.id for d in pool_devs)}, {name} params on "
                  f"{sorted(d.id for d in param_devs)}")
            check(pool_devs == devs and param_devs == devs,
                  f"group {gs.group.gid} arrays are not on its own devices")
        worst = 0.0
        for name, prompt in first_prompts(reqs).items():
            gs = node.groups[node.placement.owners(name)[0]]
            worst = max(worst, check_logits(
                gs.engine, cfg, {name: prompt},
                emit=lambda s: print(f"[{shape}] group {gs.group.gid} {s}")))
        print(f"[{shape}] logits: max error {worst:.4g} x RMS")
        node.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip serving path; 4: only the "
                    "multi-socket node phase on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the traffic")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import setup_compile_cache
    cache = setup_compile_cache()
    dev = device_summary()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print("no TPU found: this smoke test runs only on the chip",
              file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, found "
              f"{dev['count']}", file=sys.stderr)
        return 2
    print(f"compile cache: {cache}")
    try:
        if args.chips == 4:
            run_node(args.seed)
        else:
            run_one_chip(args.seed)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
