"""Multi-socket RDU-node serving (paper §III, §V-B, §VI-C).

Turns the single-device ``ServingEngine`` into a multi-socket node: the
chips of one TPU host, or sockets emulated on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``:

  * ``topology``  — carve the device set into TP x replica socket groups;
  * ``execution`` — shard_map tensor-parallel paged decode per group;
  * ``placement`` — bandwidth-driven expert -> group assignment under
    per-group HBM budgets;
  * ``scheduler`` — router-driven dispatch + node-level statistics.
"""
from repro.node.topology import (NodeTopology, SocketGroup,
                                 ensure_node_devices, make_node_topology)
from repro.node.execution import TPPagedDecodeRunner, make_group_engine
from repro.node.placement import (ExpertProfile, Placement,
                                  plan_expert_placement)
from repro.node.scheduler import GroupState, NodeStats, RDUNode

__all__ = [
    "NodeTopology", "SocketGroup", "ensure_node_devices",
    "make_node_topology",
    "TPPagedDecodeRunner", "make_group_engine",
    "ExpertProfile", "Placement", "plan_expert_placement",
    "GroupState", "NodeStats", "RDUNode",
]
