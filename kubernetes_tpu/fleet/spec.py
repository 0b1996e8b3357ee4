"""Declarative fleet specifications.

A ``FleetSpec`` says WHAT many-process cluster to run — how many
apiserver replicas, how many shard scheduler processes (and whether they
pin per core), how many hollow-plane processes split one
``HollowProfile`` by deterministic name-prefix ranges, which controller
managers ride along, and the env/wire/hint seams every child inherits.
The conductor (conductor.py) owns HOW: staged bring-up, readiness
barriers, supervision, teardown.

Specs are plain dicts on disk (JSON) so the perf harness, the CLI
(``python -m kubernetes_tpu.fleet --spec fleet.json --pods N``), and
tests share one format — docs/SCALE.md § fleet conductor documents it:

    {"name": "fleet-100k", "shards": 2, "replicas": 1,
     "mesh_devices": 8, "hollow_procs": 2,
     "hollow": {"count": 100000, "zones": 100, "heartbeat_s": 120.0,
                "drift": 0.02, "churn_per_s": 2.0},
     "env": {"TPU_SCHED_LIST_PAGE": "500"}}
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, Optional

# Per-role crash policy (the restart-policy matrix, docs/SCALE.md):
#   restart — respawn the member, counted, never silent. Hollow members
#             respawn with --adopt so they re-register their EXACT
#             name-prefix range with zero duplicate nodes.
#   adopt   — do NOT respawn: the surviving peers absorb the dead
#             member's work through an existing protocol (a crashed
#             shard's lease expires and the ring successor adopts its
#             range — a conductor respawn would race that adoption).
#   never   — record the exit and leave it down (control-plane replicas:
#             losing the leader is a FAILOVER, not a supervision event).
RESTART_POLICIES = ("restart", "adopt", "never")
DEFAULT_RESTART = {
    "apiserver": "never",
    "follower": "never",
    "shard": "adopt",
    "hollow": "restart",
    "controller": "restart",
    "workload": "restart",
    "deschedule": "restart",
}


@dataclass
class FleetSpec:
    name: str = "fleet"
    # Shard scheduler plane (`python -m kubernetes_tpu --shard-index i`).
    shards: int = 1
    shard_lease_s: float = 15.0
    pin_shards: bool = True         # taskset shard i -> core i%cores (n>1)
    # mesh_devices > 1 gives every shard a virtual device mesh
    # (XLA_FLAGS --xla_force_host_platform_device_count=N) so row-local
    # plans dispatch mesh-SPMD.
    mesh_devices: int = 0
    # Replicated control plane: follower apiservers tailing the leader.
    replicas: int = 0
    repl_lease_s: float = 2.0
    # Hollow kubelet plane: one HollowProfile dict split across
    # hollow_procs processes by deterministic name-prefix ranges
    # (HollowProfile.split — disjoint-and-complete absolute index tiles).
    hollow: Optional[dict] = None
    hollow_procs: int = 1
    # Controller managers: node-lifecycle kwargs dict and/or workload
    # manager dict ({"managers": 2, "lease_ttl": s, "tick": s,
    # "autoscale": {...}, "trace": {...}}).
    node_lifecycle: Optional[dict] = None
    workload: Optional[dict] = None
    # Descheduler managers (drift-repair plane, docs/DESCHEDULE.md):
    # {"managers": 2, "lease_ttl": s, "tick": s, "hysteresis": n,
    #  "max_moves": n, "device": bool}.
    deschedule: Optional[dict] = None
    # Environment every child inherits (the control plane's settings:
    # TPU_SCHED_WIRE, TPU_SCHED_LIST_PAGE, ...); shard_env lands on shard
    # schedulers only.
    env: Dict[str, str] = field(default_factory=dict)
    shard_env: Dict[str, str] = field(default_factory=dict)
    # Observability / durability seams.
    flightrec_dir: str = ""
    data_dir: str = ""
    fair_tenants: bool = False
    apf_workload: str = ""
    # Supervision.
    restart: Dict[str, str] = field(
        default_factory=lambda: dict(DEFAULT_RESTART))
    max_restarts: int = 3           # per member, then the conductor gives up
    supervise_interval_s: float = 0.5
    startup_timeout_s: float = 300.0

    @classmethod
    def from_dict(cls, d: dict) -> "FleetSpec":
        restart = dict(DEFAULT_RESTART)
        restart.update({str(k): str(v)
                        for k, v in dict(d.get("restart", {})).items()})
        return cls(
            name=str(d.get("name", "fleet")),
            shards=int(d.get("shards", 1)),
            shard_lease_s=float(d.get("shard_lease_s", 15.0)),
            pin_shards=bool(d.get("pin_shards", True)),
            mesh_devices=int(d.get("mesh_devices", 0)),
            replicas=int(d.get("replicas", 0)),
            repl_lease_s=float(d.get("repl_lease_s", 2.0)),
            hollow=(dict(d["hollow"]) if d.get("hollow") else None),
            hollow_procs=int(d.get("hollow_procs", 1)),
            node_lifecycle=(dict(d["node_lifecycle"])
                            if d.get("node_lifecycle") else None),
            workload=(dict(d["workload"]) if d.get("workload") else None),
            deschedule=(dict(d["deschedule"])
                        if d.get("deschedule") else None),
            env={str(k): str(v) for k, v in dict(d.get("env", {})).items()},
            shard_env={str(k): str(v)
                       for k, v in dict(d.get("shard_env", {})).items()},
            flightrec_dir=str(d.get("flightrec_dir", "")),
            data_dir=str(d.get("data_dir", "")),
            fair_tenants=bool(d.get("fair_tenants", False)),
            apf_workload=str(d.get("apf_workload", "")),
            restart=restart,
            max_restarts=int(d.get("max_restarts", 3)),
            supervise_interval_s=float(d.get("supervise_interval_s", 0.5)),
            startup_timeout_s=float(d.get("startup_timeout_s", 300.0)),
        )

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "shards": self.shards,
            "shard_lease_s": self.shard_lease_s,
            "pin_shards": self.pin_shards,
            "mesh_devices": self.mesh_devices,
            "replicas": self.replicas,
            "repl_lease_s": self.repl_lease_s,
            "hollow": dict(self.hollow) if self.hollow else None,
            "hollow_procs": self.hollow_procs,
            "node_lifecycle": (dict(self.node_lifecycle)
                               if self.node_lifecycle else None),
            "workload": dict(self.workload) if self.workload else None,
            "deschedule": (dict(self.deschedule)
                           if self.deschedule else None),
            "env": dict(self.env),
            "shard_env": dict(self.shard_env),
            "flightrec_dir": self.flightrec_dir,
            "data_dir": self.data_dir,
            "fair_tenants": self.fair_tenants,
            "apf_workload": self.apf_workload,
            "restart": dict(self.restart),
            "max_restarts": self.max_restarts,
            "supervise_interval_s": self.supervise_interval_s,
            "startup_timeout_s": self.startup_timeout_s,
        }

    @classmethod
    def load(cls, path: str) -> "FleetSpec":
        with open(path) as fh:
            return cls.from_dict(json.load(fh))

    def validate(self) -> "FleetSpec":
        """Raise ValueError on an unrunnable spec (the conductor calls
        this before spawning anything — a bad spec must fail at stage
        zero, not as a half-up fleet)."""
        if self.shards < 1:
            raise ValueError("spec.shards must be >= 1")
        if self.replicas < 0:
            raise ValueError("spec.replicas must be >= 0")
        if self.hollow_procs < 1:
            raise ValueError("spec.hollow_procs must be >= 1")
        if self.mesh_devices < 0:
            raise ValueError("spec.mesh_devices must be >= 0")
        if self.max_restarts < 0:
            raise ValueError("spec.max_restarts must be >= 0")
        if self.supervise_interval_s <= 0:
            raise ValueError("spec.supervise_interval_s must be > 0")
        if self.startup_timeout_s <= 0:
            raise ValueError("spec.startup_timeout_s must be > 0")
        for role, policy in self.restart.items():
            if policy not in RESTART_POLICIES:
                raise ValueError(
                    f"spec.restart[{role!r}] = {policy!r}: must be one of "
                    f"{RESTART_POLICIES}")
        if self.hollow is not None:
            from ..hollow import HollowProfile
            prof = HollowProfile.from_dict(self.hollow)
            if prof.count < 1:
                raise ValueError("spec.hollow.count must be >= 1")
            if self.hollow_procs > prof.count:
                raise ValueError("spec.hollow_procs exceeds hollow.count")
        if self.workload is not None \
                and int(self.workload.get("managers", 2)) < 1:
            raise ValueError("spec.workload.managers must be >= 1")
        if self.deschedule is not None \
                and int(self.deschedule.get("managers", 2)) < 1:
            raise ValueError("spec.deschedule.managers must be >= 1")
        return self
