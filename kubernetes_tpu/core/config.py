"""ComponentConfig: typed scheduler configuration.

Re-expresses KubeSchedulerConfiguration (pkg/scheduler/apis/config/types.go:37
+ v1 defaults in apis/config/v1/default_plugins.go / defaults.go): profiles
with per-extension-point plugin enable/disable + weights + typed plugin args,
percentageOfNodesToScore, backoff bounds, feature gates, and the TPU batch
knobs that replace `parallelism` (the 16-goroutine fan-out has no meaning on
device — SURVEY.md §2.4).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

from .features import FeatureGates
from .registry import DEFAULT_PLUGINS


@dataclass
class PluginSet:
    """Enabled/disabled plugin overlay (config/types.go Plugins): the default
    set, minus `disabled` names ("*" clears it), plus `enabled` (name, weight)
    entries appended in order."""

    enabled: Tuple[Tuple[str, int], ...] = ()
    disabled: Tuple[str, ...] = ()

    def resolve(self, defaults: Sequence[Tuple[str, int]] = DEFAULT_PLUGINS) -> Tuple[Tuple[str, int], ...]:
        if "*" in self.disabled:
            base: List[Tuple[str, int]] = []
        else:
            base = [(n, w) for n, w in defaults if n not in self.disabled]
        names = {n for n, _ in base}
        out = list(base)
        for name, weight in self.enabled:
            if name in names:
                out = [(n, weight if n == name else w) for n, w in out]
            else:
                out.append((name, weight))
        return tuple(out)


@dataclass
class ProfileConfig:
    """config/types.go KubeSchedulerProfile."""

    scheduler_name: str = "default-scheduler"
    plugins: PluginSet = field(default_factory=PluginSet)
    plugin_config: Dict[str, dict] = field(default_factory=dict)  # name -> args


@dataclass
class SchedulerConfiguration:
    """KubeSchedulerConfiguration (types.go:37)."""

    profiles: List[ProfileConfig] = field(default_factory=lambda: [ProfileConfig()])
    percentage_of_nodes_to_score: int = 0         # types.go:62-70 (0 = adaptive)
    pod_initial_backoff_seconds: float = 1.0      # scheduling_queue.go:78-82
    pod_max_backoff_seconds: float = 10.0
    feature_gates: Dict[str, bool] = field(default_factory=dict)
    # TPU batch knobs (replace `parallelism`, types.go:48-49).
    max_batch: int = 1024
    extenders: List[dict] = field(default_factory=list)
    # Async API writes run on a worker thread (the reference's dispatcher
    # goroutine) whenever the clientset's writes cross a socket: the
    # scheduler sees that for itself (core/scheduler.py _dispatch_mode), and
    # over the in-process store they run inline, for determinism. Set, this
    # forces the worker over an in-process clientset too (tests, and the
    # perf harness's simulated round trip).
    async_dispatch_threads: bool = False
    # Per-tenant weighted fair dequeue on the pending queue (core/queue.py
    # _FairTenantHeap; docs/RESILIENCE.md § overload & fairness). Off by
    # default — single-tenant workloads keep the global queue-sort order.
    fair_tenant_dequeue: bool = False
    tenant_weights: Dict[str, float] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Mapping) -> "SchedulerConfiguration":
        profiles = []
        for p in d.get("profiles", [{}]):
            plugins = p.get("plugins", {})
            profiles.append(ProfileConfig(
                scheduler_name=p.get("schedulerName", "default-scheduler"),
                plugins=PluginSet(
                    enabled=tuple(
                        (e["name"], e.get("weight", 1)) if isinstance(e, dict) else (e, 1)
                        for e in plugins.get("enabled", ())),
                    disabled=tuple(plugins.get("disabled", ())),
                ),
                plugin_config={
                    pc["name"]: pc.get("args", {}) for pc in p.get("pluginConfig", ())
                },
            ))
        return cls(
            profiles=profiles or [ProfileConfig()],
            percentage_of_nodes_to_score=d.get("percentageOfNodesToScore", 0),
            pod_initial_backoff_seconds=d.get("podInitialBackoffSeconds", 1.0),
            pod_max_backoff_seconds=d.get("podMaxBackoffSeconds", 10.0),
            feature_gates=dict(d.get("featureGates", {})),
            max_batch=d.get("maxBatch", 1024),
            extenders=list(d.get("extenders", ())),
            async_dispatch_threads=bool(d.get("asyncDispatchThreads", False)),
            fair_tenant_dequeue=bool(d.get("fairTenantDequeue", False)),
            tenant_weights=dict(d.get("tenantWeights", {})),
        )

    def gates(self) -> FeatureGates:
        return FeatureGates(self.feature_gates)

    def validate(self) -> List[str]:
        """ValidateKubeSchedulerConfiguration
        (apis/config/validation/validation.go:38): returns field errors
        ("" = valid). The TPU fork drops parallelism/leader-election knobs
        (the batch kernel replaces the goroutine pool; leases are internal),
        so those reference checks have no analogue here."""
        errs: List[str] = []
        if not (0 <= self.percentage_of_nodes_to_score <= 100):
            errs.append(
                f"percentageOfNodesToScore: {self.percentage_of_nodes_to_score}"
                " not in valid range [0-100]")
        if self.pod_initial_backoff_seconds <= 0:
            errs.append("podInitialBackoffSeconds: must be greater than 0")
        if self.pod_max_backoff_seconds < self.pod_initial_backoff_seconds:
            errs.append("podMaxBackoffSeconds: must be greater than or equal"
                        " to podInitialBackoffSeconds")
        if self.max_batch <= 0:
            errs.append("maxBatch: should be an integer value greater than zero")
        if not self.profiles:
            errs.append("profiles: Required value")
        seen: Dict[str, int] = {}
        for i, p in enumerate(self.profiles):
            if not p.scheduler_name:
                errs.append(f"profiles[{i}].schedulerName: Required value")
            if p.scheduler_name in seen:
                errs.append(
                    f"profiles[{i}].schedulerName: Duplicate value "
                    f"{p.scheduler_name!r} (first at profiles[{seen[p.scheduler_name]}])")
            else:
                seen[p.scheduler_name] = i
        for i, e in enumerate(self.extenders):
            if not isinstance(e, Mapping):
                continue  # pre-built Extender objects validate themselves
            if not e.get("urlPrefix"):
                errs.append(f"extenders[{i}].urlPrefix: Required value")
            if not any(e.get(v) for v in
                       ("filterVerb", "prioritizeVerb", "bindVerb",
                        "preemptVerb")):
                errs.append(f"extenders[{i}]: must configure at least one verb")
            w = e.get("weight", 1)
            if not isinstance(w, int) or w <= 0:
                errs.append(f"extenders[{i}].weight: must be a positive integer")
        return errs
