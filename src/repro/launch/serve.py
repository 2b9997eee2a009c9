"""CoE serving driver: ``python -m repro.launch.serve [...]``.

Builds a Samba-CoE-style composition (router + N experts derived from one
backbone config), loads all experts on the capacity tier (host DRAM = the
paper's DDR), and serves batched requests through the continuous-batching
engine over the three-tier switching engine and paged KV pool. Reports the
paper's Fig-1 breakdown (switch vs execute) and cache statistics.

Requests demonstrate both routing paths: most arrive untagged
(``expert=None``) and are routed by the composition's router at submit;
a ``--tagged-fraction`` of them arrive caller-tagged and keep their tag.

``--node-shape TPxG`` serves through a multi-socket RDU node instead
(``repro.node``): TP x G socket groups emulated on CPU devices, e.g.

    python -m repro.launch.serve --node-shape 2x4 --reduced

Lifecycle plane (``repro.obs``): ``--metrics-port`` additionally mounts
``/readyz`` (503 until ``warmup()`` completes) and ``/debug/*`` snapshots;
``--watchdog`` starts the background invariant sampler; ``SIGUSR2`` (or a
watchdog anomaly) dumps the flight recorder's postmortem bundle to
``--flight-out``.
"""
from __future__ import annotations

import argparse
import signal
import time

import numpy as np


def install_flight_dump_signal(path, registry=None, signum=None):
    """Install a signal handler (default ``SIGUSR2``) that dumps the process
    flight recorder's postmortem bundle to ``path``. Returns the signal
    number installed, or ``None`` on platforms without SIGUSR2. Tests drive
    it in-process via ``signal.raise_signal``."""
    from repro.obs import flightrec, get_registry

    if signum is None:
        signum = getattr(signal, "SIGUSR2", None)
        if signum is None:                 # e.g. Windows
            return None
    reg = registry if registry is not None else get_registry()

    def _dump(_sig, _frame):
        out = flightrec.dump(path, reg, reason="signal")
        print(f"flight recorder: postmortem bundle -> {out}")

    signal.signal(signum, _dump)
    return signum


def _wire_obs(args, server, ready, providers, engines):
    """Hook one serve target into the lifecycle plane: mount its debug
    snapshots on the httpd and the flight recorder, flip ``/readyz`` to the
    target's warmed state, and (``--watchdog``) start the invariant
    sampler. Returns the watchdog (or None)."""
    from repro.obs import Watchdog, flightrec

    for name, fn in providers.items():
        flightrec.add_state_provider(name, fn)
        if server is not None:
            server.add_debug(name, fn)
    if ready is not None:
        ready["fn"] = lambda: all(getattr(e, "warmed", False)
                                  for e in engines)
    if not args.watchdog:
        return None
    wd = Watchdog(engines, interval_s=args.watchdog_interval,
                  dump_path=args.flight_out)
    wd.start()
    return wd


def build_coe(cfg, n_experts: int, hbm_experts: float, seed: int = 0,
              registry=None):
    """Create n_experts fine-tune-style variants of one backbone (the paper
    derives all 150 experts from Llama2-7B). ``hbm_experts`` is the HBM
    tier capacity in units of one expert. ``registry`` publishes the weight
    cache's metrics into a shared ``MetricsRegistry`` (``--metrics-port``)."""
    from repro.core import CompositionOfExperts, ExpertHandle, HashRouter

    hosts, nbytes = build_experts(cfg, n_experts, seed)
    coe = CompositionOfExperts(
        HashRouter(n_experts), None,
        hbm_capacity_bytes=int(max(1.0, hbm_experts) * nbytes),
        registry=registry)
    for name, host, domain in hosts:
        coe.register(ExpertHandle(name, cfg, host, domain=domain))
    return coe, nbytes


def build_experts(cfg, n_experts: int, seed: int = 0):
    """Host-side expert pytrees: cheap fine-tune stand-ins (per-expert
    perturbations of one base init). The base is initialized and each
    expert perturbed on the device one leaf at a time, so host RAM holds
    only the finished experts (no float64 temporaries)."""
    import jax
    import jax.numpy as jnp
    from repro.models import get_model

    rng = jax.random.PRNGKey(seed)
    leaves, treedef = jax.tree.flatten(get_model(cfg).init(rng))
    nbytes = sum(x.nbytes for x in leaves)

    @jax.jit
    def perturb(x, key):
        noise = jax.random.normal(key, x.shape, jnp.float32) * 0.01
        return x + noise.astype(x.dtype)

    domains = ["code", "math", "translate", "chat", "legal", "medical"]
    out = []
    for i in range(n_experts):
        keys = jax.random.split(jax.random.fold_in(rng, i + 1), len(leaves))
        host = [np.asarray(perturb(x, k))
                if jnp.issubdtype(x.dtype, jnp.floating) else np.asarray(x)
                for x, k in zip(leaves, keys)]
        out.append((f"expert{i:03d}", jax.tree.unflatten(treedef, host),
                    domains[i % len(domains)]))
    return out, nbytes


def _make_requests(args, cfg, expert_names):
    """Request list, ``--tagged-fraction`` of them caller-tagged round-robin
    (the rest routed by the composition at submit)."""
    from repro.serving import Request

    rs = np.random.RandomState(0)
    n_tagged = int(args.requests * args.tagged_fraction)
    # --shared-prefix: every prompt opens with the same system-prompt
    # tokens (what --prefix-sharing engines dedup via the PrefixIndex)
    shared = rs.randint(0, cfg.vocab_size,
                        (getattr(args, "shared_prefix", 0),)).astype(np.int32)
    reqs = []
    for i in range(args.requests):
        tag = expert_names[i % len(expert_names)] if i < n_tagged else None
        unique = max(1, args.prompt_len - len(shared))
        toks = np.concatenate([
            shared,
            rs.randint(0, cfg.vocab_size, (unique,)).astype(np.int32)])
        reqs.append(Request(
            rid=i, tokens=toks,
            max_new_tokens=args.new_tokens, expert=tag))
    return reqs, n_tagged


def _serve_single(args, cfg, server=None, ready=None):
    from repro.obs import get_registry
    from repro.serving import ServingEngine

    # publish every engine/cache/ledger series into the process default
    # registry — what --metrics-port serves
    coe, nbytes = build_coe(cfg, args.n_experts, args.hbm_experts,
                            registry=get_registry())
    engine = ServingEngine(coe, cfg,
                           max_len=args.prompt_len + args.new_tokens,
                           n_slots=args.n_slots, block_size=8,
                           scheduler=args.scheduler,
                           backend=args.backend,
                           prefill_mode=args.prefill_mode,
                           prefix_sharing=args.prefix_sharing,
                           registry=get_registry())
    wd = _wire_obs(args, server, ready, engine.debug_providers(), [engine])
    if args.warmup:
        engine.warmup()
    reqs, n_tagged = _make_requests(args, cfg, coe.expert_names())
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    done = engine.drain()
    wall = time.perf_counter() - t0
    st = engine.stats
    print(f"served {len(done)} requests in {wall:.2f}s "
          f"({st.tokens_out} tokens, {st.tokens_per_second:.1f} tok/s); "
          f"{n_tagged} caller-tagged, {len(done) - n_tagged} router-routed")
    print(f"breakdown: route={st.route_s:.3f}s switch={st.switch_s:.3f}s "
          f"prefill={st.prefill_s:.3f}s decode={st.exec_s:.3f}s "
          f"(paper Fig-1 split)")
    print(f"scheduler: {st.decode_rounds} rounds, "
          f"occupancy {st.mean_occupancy:.2f}, {st.switches} switches")
    print(f"weight cache: {coe.cache.stats}")
    print(f"kv pool: {engine.pool.stats}")
    if args.prefix_sharing:
        print(f"prefix sharing: {st.prefix_hit_tokens} prompt tokens "
              f"adopted from shared KV, "
              f"{engine.pool.stats.cow_splits} COW splits, "
              f"{len(engine.prefix_index)} indexed blocks")
        engine.release_shared()
    print(f"tier ledger: overlap={coe.cache.ledger.overlap_ratio:.2f} "
          f"store_read={coe.cache.ledger.bytes_moved('store_read')}B "
          f"h2d={coe.cache.ledger.bytes_moved('h2d')}B")
    if engine.slo.tenants():
        print(f"slo: attainment={engine.slo.attainment():.3f} "
              f"goodput={engine.slo.goodput():.1f} tok/s")
    if wd is not None:
        wd.stop()
    return engine


def _serve_node(args, cfg, server=None, ready=None):
    from repro.core import HashRouter
    from repro.node import make_node_topology, RDUNode
    from repro.obs import get_registry

    tp, n_groups = (int(x) for x in args.node_shape.split("x"))
    topo = make_node_topology(tp, n_groups)
    hosts, nbytes = build_experts(cfg, args.n_experts)
    node = RDUNode(topo, cfg, HashRouter(args.n_experts), None,
                   group_hbm_bytes=int(max(1.0, args.hbm_experts) * nbytes),
                   group_kv_reserve_bytes=int(0.8 * nbytes),
                   n_slots=max(1, args.n_slots // n_groups), block_size=8,
                   max_len=args.prompt_len + args.new_tokens,
                   scheduler=args.scheduler,
                   backend=args.backend,
                   prefill_mode=args.prefill_mode,
                   prefix_sharing=args.prefix_sharing,
                   prefill_groups=args.prefill_groups,
                   registry=get_registry())
    for name, host, domain in hosts:
        node.register_expert(name, host, domain=domain)
    placement = node.plan()
    wd = _wire_obs(args, server, ready, node.debug_providers(),
                   node.engines())
    if args.warmup:
        node.warmup()
    reqs, n_tagged = _make_requests(args, cfg, node.expert_names())
    t0 = time.perf_counter()
    for r in reqs:
        node.submit(r)
    done = node.drain()
    wall = time.perf_counter() - t0
    st = node.stats()
    print(f"[node {topo.name}] served {len(done)} requests in {wall:.2f}s "
          f"({st.tokens_out} tokens, {st.tokens_per_second(wall):.1f} tok/s);"
          f" {n_tagged} caller-tagged, {len(done) - n_tagged} router-routed")
    print(f"route={st.route_s:.3f}s switch_stall={st.switch_stall_s:.3f}s "
          f"imbalance={st.imbalance:.2f} "
          f"spilled_experts={len(placement.spilled)}")
    for g in st.per_group:
        print(f"  group {g['gid']} (tp={g['tp']}): {g['requests']} req / "
              f"{g['tokens_out']} tok, occupancy {g['occupancy']:.2f}, "
              f"{g['switches']} switches, cache h/m "
              f"{g['cache_hits']}/{g['cache_misses']}")
    if wd is not None:
        wd.stop()
    node.close()
    return node


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="samba-coe-expert-7b")
    ap.add_argument("--n-experts", type=int, default=8)
    ap.add_argument("--hbm-experts", type=float, default=2.5,
                    help="HBM tier capacity in units of one expert "
                    "(per socket group in --node-shape mode)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--n-slots", type=int, default=8,
                    help="decode slots (split across groups in node mode)")
    ap.add_argument("--scheduler", default="continuous",
                    choices=["continuous", "run_to_completion"])
    ap.add_argument("--backend", default="xla", choices=["xla", "fused"],
                    help="decode-step backend (serving/backends.py): 'xla' "
                    "is the reference paged extend, 'fused' runs each layer "
                    "as paged-native Pallas kernels (prologue / paged "
                    "flash-decode / epilogue)")
    ap.add_argument("--prefill-mode", default="packed",
                    choices=["packed", "sequential"],
                    help="'packed' admits pending requests through the "
                    "bucketed AOT packed-prefill path (serving/prefill.py; "
                    "zero recompiles after warmup); 'sequential' keeps the "
                    "one-forward-per-prompt reference path")
    ap.add_argument("--prefill-groups", type=int, default=0, metavar="N",
                    help="with --node-shape: dedicate the first N socket "
                    "groups to prefill (disaggregated serving) — their KV "
                    "blocks are handed off to the decode groups")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="enable copy-on-write prefix sharing + session "
                    "retention in the engine(s): shared prompt prefixes "
                    "prefill once and later requests adopt the KV blocks "
                    "read-only (serving/kvcache.py PrefixIndex)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="open every generated prompt with the same N "
                    "system-prompt tokens (the workload --prefix-sharing "
                    "dedups)")
    ap.add_argument("--tagged-fraction", type=float, default=0.25,
                    help="fraction of requests submitted caller-tagged; "
                    "the rest are routed by the composition's router")
    ap.add_argument("--node-shape", default=None, metavar="TPxG",
                    help="serve through a TP x G socket-group RDU node "
                    "(e.g. 2x4) instead of the single-device engine")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve the metrics registry over HTTP while "
                    "running: /metrics (Prometheus text), /metrics.json "
                    "(flat snapshot), /healthz. PORT 0 binds an ephemeral "
                    "port (printed at startup)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record request-lifecycle spans and export a "
                    "Chrome-trace / Perfetto JSON to PATH on exit "
                    "(open at https://ui.perfetto.dev)")
    ap.add_argument("--flight-out", default="flight_dump.json",
                    metavar="PATH",
                    help="where SIGUSR2 (and watchdog anomalies) dump the "
                    "flight recorder's postmortem bundle")
    ap.add_argument("--watchdog", action="store_true",
                    help="start the background invariant sampler "
                    "(obs.watchdog): stuck requests, KV refcount leaks, "
                    "HBM budget, queue age -> obs.anomaly{kind=} + a "
                    "postmortem dump to --flight-out")
    ap.add_argument("--watchdog-interval", type=float, default=1.0,
                    metavar="S", help="watchdog sampling interval")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile the serving hot path before traffic "
                    "(flips /readyz from 503 to 200 on completion)")
    args = ap.parse_args(argv)

    if args.node_shape:
        # the CPU socket count must land before the backend initializes
        from repro.node.topology import ensure_node_devices
        tp, n_groups = (int(x) for x in args.node_shape.split("x"))
        ensure_node_devices(tp * n_groups)

    from repro.configs import get_config, pad_for_tp, reduced
    from repro.launch.compile_cache import setup_compile_cache
    from repro.obs import get_registry, serve_metrics, trace

    print(f"compile cache: {setup_compile_cache()}")
    install_flight_dump_signal(args.flight_out)

    # the engine/node is built after the httpd starts; /readyz reads the
    # warmed state through this mutable slot once _wire_obs fills it in
    ready = {"fn": lambda: False}
    server = None
    if args.metrics_port is not None:
        server = serve_metrics(get_registry(), port=args.metrics_port,
                               ready_check=lambda: ready["fn"]())
        print(f"metrics: {server.url}/metrics "
              f"(+ /metrics.json, /healthz, /readyz, /debug/*)")
    if args.trace_out:
        trace.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    try:
        if args.node_shape:
            cfg = pad_for_tp(cfg, int(args.node_shape.split("x")[0]))
            return _serve_node(args, cfg, server, ready)
        return _serve_single(args, cfg, server, ready)
    finally:
        if args.trace_out:
            trace.disable()
            path = trace.export(args.trace_out)
            print(f"trace: {len(trace.events())} events -> {path}")
        if server is not None:
            server.stop()


if __name__ == "__main__":
    main()
