"""Socket-group topology for one multi-socket RDU node (paper §III / §VI-C).

The paper's 8-socket node runs each expert tensor-parallel over a dedicated
inter-RDU network; the node may also be carved into several independent
TP groups, each serving its own expert working set. We model that carve as
``TP degree x replica count`` over the host's device list: a node of 8
devices can run as one TP=8 group (``8x1``), four TP=2 groups (``2x4``),
eight TP=1 groups (``1x8``), and so on. Each group gets its own one-axis
``('model',)`` JAX mesh over a disjoint device subset — the inter-RDU TP
domain — while the shared ``ExpertStore`` plays the node-wide DDR tier.

On a TPU host the groups are carved from the real chips. On the CPU,
``ensure_node_devices`` (or ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)
emulates 8 "sockets" on one host.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh

from repro.launch.mesh import make_device_mesh


def ensure_node_devices(n: int):
    """Make ``n`` devices visible for a node shape, before anything touches
    the JAX backend. On the CPU backend the sockets are emulated
    (``jax_num_cpu_devices``, which only the CPU client reads); on an
    accelerator the node runs on the real chips, and a shape that needs more
    than are present fails with the count. Node drivers
    (``launch/serve.py --node-shape``, ``benchmarks/run.py --sweep-node``)
    share this bootstrap."""
    try:
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        pass            # backend already initialized: the count check decides
    devs = jax.devices()
    if len(devs) >= n:
        return
    if devs[0].platform == "cpu":
        raise SystemExit(
            f"{n} emulated sockets requested but the JAX backend already "
            f"initialized with {len(devs)} device(s); launch with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    raise SystemExit(
        f"node shape needs {n} chips but {len(devs)} {devs[0].platform} "
        f"device(s) ({devs[0].device_kind}) are present")


@dataclass(frozen=True)
class SocketGroup:
    """One TP domain: ``tp`` sockets behind a single serving engine."""
    gid: int
    tp: int
    mesh: Mesh

    @property
    def devices(self) -> Tuple:
        return tuple(self.mesh.devices.flat)


@dataclass(frozen=True)
class NodeTopology:
    tp: int
    n_groups: int
    groups: Tuple[SocketGroup, ...]

    @property
    def n_sockets(self) -> int:
        return self.tp * self.n_groups

    @property
    def name(self) -> str:
        return f"{self.tp}x{self.n_groups}"


def make_node_topology(tp: int, n_groups: Optional[int] = None,
                       devices: Optional[Sequence] = None) -> NodeTopology:
    """Carve the device list into ``n_groups`` disjoint TP-``tp`` socket
    groups (default: as many groups as the devices allow). Group ``g`` owns
    devices ``[g*tp, (g+1)*tp)`` — contiguous, like the paper's pairs of
    sockets sharing a DDR channel group."""
    devs = list(devices if devices is not None else jax.devices())
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if n_groups is None:
        n_groups = len(devs) // tp
    if n_groups < 1:
        raise ValueError(f"n_groups must be >= 1, got {n_groups}")
    need = tp * n_groups
    if need > len(devs):
        raise ValueError(
            f"topology {tp}x{n_groups} needs {need} devices but only "
            f"{len(devs)} are visible — emulate more sockets with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    groups = tuple(
        SocketGroup(g, tp, make_device_mesh((tp,), ("model",),
                                            devs[g * tp:(g + 1) * tp]))
        for g in range(n_groups))
    return NodeTopology(tp=tp, n_groups=n_groups, groups=groups)
