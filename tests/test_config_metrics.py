"""ComponentConfig, feature gates, and metrics (SURVEY.md §5)."""

import pytest

from kubernetes_tpu.core.config import PluginSet, ProfileConfig, SchedulerConfiguration
from kubernetes_tpu.core.features import (
    FeatureGates,
    GENERIC_WORKLOAD,
    TPU_BATCH_SCHEDULING,
    TPU_STATE_RESIDENCY,
)
from kubernetes_tpu.core.scheduler import Scheduler
from kubernetes_tpu.models.tpu_scheduler import TPUScheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod


class TestFeatureGates:
    def test_defaults(self):
        g = FeatureGates()
        assert g.enabled(GENERIC_WORKLOAD)
        assert g.enabled(TPU_BATCH_SCHEDULING)

    def test_override_and_unknown(self):
        g = FeatureGates({TPU_BATCH_SCHEDULING: False, TPU_STATE_RESIDENCY: False})
        assert not g.enabled(TPU_BATCH_SCHEDULING)
        with pytest.raises(ValueError):
            FeatureGates({"NoSuchGate": True})

    def test_dependency_validation(self):
        with pytest.raises(ValueError):
            FeatureGates({TPU_BATCH_SCHEDULING: False})  # residency depends on it


class TestComponentConfig:
    def test_plugin_set_resolve(self):
        ps = PluginSet(enabled=(("TaintToleration", 5),), disabled=("ImageLocality",))
        resolved = dict(ps.resolve())
        assert resolved["TaintToleration"] == 5
        assert "ImageLocality" not in resolved

    def test_from_dict_profile(self):
        cfg = SchedulerConfiguration.from_dict({
            "profiles": [{
                "schedulerName": "custom",
                "plugins": {"disabled": ["InterPodAffinity"]},
                "pluginConfig": [
                    {"name": "NodeResourcesFit",
                     "args": {"scoring_strategy": "MostAllocated"}}],
            }],
            "percentageOfNodesToScore": 20,
            "featureGates": {"GenericWorkload": True},
        })
        s = Scheduler(config=cfg)
        assert "custom" in s.profiles
        fw = s.profiles["custom"]
        assert fw.plugin("InterPodAffinity") is None
        assert fw.plugin("NodeResourcesFit").scoring_strategy == "MostAllocated"
        assert s.percentage_of_nodes_to_score == 20

    def test_custom_profile_schedules(self):
        cfg = SchedulerConfiguration.from_dict({
            "profiles": [{"schedulerName": "custom"}]})
        s = Scheduler(config=cfg)
        s.clientset.create_node(
            make_node().name("n0").capacity({"cpu": "4", "pods": 10}).obj())
        p = make_pod().name("p").req({"cpu": "1"}).scheduler_name("custom").obj()
        s.clientset.create_pod(p)
        s.run_until_idle()
        assert s.scheduled == 1

    def test_device_gate_off_uses_host_path(self):
        cfg = SchedulerConfiguration.from_dict({
            "featureGates": {"TPUBatchScheduling": False,
                             "TPUStateResidency": False}})
        s = TPUScheduler(config=cfg)
        s.clientset.create_node(
            make_node().name("n0").capacity({"cpu": "4", "pods": 10}).obj())
        s.clientset.create_pod(make_pod().name("p").req({"cpu": "1"}).obj())
        s.run_until_idle()
        assert s.scheduled == 1
        assert s.device_batches == 0


class TestMetrics:
    def test_schedule_attempt_series(self):
        s = Scheduler()
        s.clientset.create_node(
            make_node().name("n0").capacity({"cpu": "2", "pods": 10}).obj())
        s.clientset.create_pod(make_pod().name("fits").req({"cpu": "1"}).obj())
        s.clientset.create_pod(make_pod().name("huge").req({"cpu": "64"}).obj())
        s.run_until_idle()
        m = s.metrics
        assert m.schedule_attempts.value("scheduled", "default-scheduler") == 1
        assert m.schedule_attempts.value("unschedulable", "default-scheduler") >= 1
        assert m.scheduling_attempt_duration.count("scheduled", "default-scheduler") == 1
        text = s.expose_metrics()
        assert "scheduler_schedule_attempts_total" in text
        assert 'scheduler_pending_pods{queue="unschedulable"}' in text

    def test_preemption_metrics(self):
        s = Scheduler()
        s.clientset.create_node(
            make_node().name("n0").capacity({"cpu": "2", "pods": 10}).obj())
        s.clientset.create_pod(make_pod().name("low").req({"cpu": "2"}).priority(1).obj())
        s.run_until_idle()
        s.clientset.create_pod(make_pod().name("hi").req({"cpu": "2"}).priority(9).obj())
        s.run_until_idle()
        assert s.metrics.preemption_attempts.value() >= 1
        assert s.metrics.preemption_victims.count() == 1

    def test_batch_metrics(self):
        s = TPUScheduler()
        s.clientset.create_node(
            make_node().name("n0").capacity({"cpu": "8", "pods": 20}).obj())
        for i in range(5):
            s.clientset.create_pod(make_pod().name(f"p{i}").req({"cpu": "1"}).obj())
        s.run_until_idle()
        assert s.metrics.batch_attempts.value("dispatched") >= 1
        assert s.metrics.batch_size.count() >= 1


def test_pre_bind_pre_flight_skips_and_runs():
    """PreBindPreFlight (runtime/framework.go:1875): all-Skip bypasses the
    PreBind phase; a declaring plugin still runs when it has work."""
    from kubernetes_tpu.core.framework import CycleState, Framework, OK, Status

    ran = []

    class Flighty:
        name = "Flighty"

        def __init__(self, skip):
            self._skip = skip

        def pre_bind_pre_flight(self, state, pod, node):
            return Status.skip() if self._skip else OK

        def pre_bind(self, state, pod, node):
            ran.append(self.name)
            return OK

    from kubernetes_tpu.testing.wrappers import make_pod
    pod = make_pod().name("p").obj()

    fw = Framework(plugins=[(Flighty(skip=True), 0)])
    state = CycleState()
    st = fw.run_pre_bind_pre_flight(state, pod, "n0")
    assert st.is_skip()
    assert "Flighty" in state.skip_pre_bind_plugins

    fw2 = Framework(plugins=[(Flighty(skip=False), 0)])
    state2 = CycleState()
    st2 = fw2.run_pre_bind_pre_flight(state2, pod, "n0")
    assert st2.is_success() and not st2.is_skip()
    fw2.run_pre_bind_plugins(state2, pod, "n0")
    assert ran == ["Flighty"]


def test_extension_point_latency_recorded():
    """framework_extension_point_duration_seconds fills per point during
    host scheduling cycles (metrics.go:265-615 series; perf artifact
    carries per-point percentiles)."""
    from kubernetes_tpu.core.clientset import FakeClientset
    from kubernetes_tpu.core.scheduler import Scheduler
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    cs = FakeClientset()
    sched = Scheduler(clientset=cs)
    cs.create_node(make_node().name("n0").capacity({"cpu": "4", "pods": 10}).obj())
    cs.create_node(make_node().name("n1").capacity({"cpu": "4", "pods": 10}).obj())
    cs.create_pod(make_pod().name("p").req({"cpu": "1"}).obj())
    sched.run_until_idle()
    hist = sched.metrics.framework_extension_point_duration
    for point in ("PreFilter", "Filter", "PreScore", "Score", "Reserve",
                  "Permit", "Bind"):
        assert hist.count(point, "Success", "") >= 1, point


def test_metric_async_recorder_flushes_off_thread():
    """metric_recorder.go analogue: observations buffer on the hot path and
    land in the histogram via the flusher thread; overflow drops are
    counted, close() drains."""
    import time as _t

    from kubernetes_tpu.core.metrics import Histogram, MetricAsyncRecorder

    h = Histogram("test_hist", "t", ("label",))
    rec = MetricAsyncRecorder(interval=0.01, capacity=8)
    for i in range(6):
        rec.observe(h, 0.001 * i, "x")
    deadline = _t.monotonic() + 5
    while _t.monotonic() < deadline and h.count("x") < 6:
        _t.sleep(0.005)
    assert h.count("x") == 6
    # overflow drops (non-blocking send semantics)
    rec._stop.set(); rec._thread.join(timeout=2)  # park the flusher
    for i in range(20):
        rec.observe(h, 0.1, "x")
    assert rec.dropped == 12
    rec.flush_now()
    assert h.count("x") == 14


def test_scheduler_configuration_validation():
    """ValidateKubeSchedulerConfiguration (validation.go:38): range checks,
    profile uniqueness, extender verb/weight requirements."""
    from kubernetes_tpu.core.config import ProfileConfig, SchedulerConfiguration

    assert SchedulerConfiguration().validate() == []

    bad = SchedulerConfiguration(
        percentage_of_nodes_to_score=150,
        pod_initial_backoff_seconds=0,
        pod_max_backoff_seconds=-1,
        max_batch=0,
        profiles=[ProfileConfig(scheduler_name="a"),
                  ProfileConfig(scheduler_name="a")],
        extenders=[{"filterVerb": "filter"},         # no urlPrefix
                   {"urlPrefix": "http://x", "weight": 0}])  # no verb, bad weight
    errs = bad.validate()
    joined = "\n".join(errs)
    assert "percentageOfNodesToScore" in joined
    assert "podInitialBackoffSeconds" in joined
    assert "podMaxBackoffSeconds" in joined
    assert "maxBatch" in joined
    assert "Duplicate" in joined
    assert "urlPrefix" in joined
    assert "at least one verb" in joined
    assert "positive integer" in joined


REFERENCE_SERIES = {
    # pkg/scheduler/metrics/metrics.go:265-615 — all 45 registered names
    # (grep 'Name:' over the file), prefixed scheduler_ by the subsystem.
    "async_api_call_execution_duration_seconds",
    "async_api_call_execution_total",
    "batch_attempts_total",
    "batch_cache_flushed_total",
    "cache_size",
    "dra_bindingconditions_allocations_total",
    "dra_bindingconditions_wait_duration_seconds",
    "event_handling_duration_seconds",
    "framework_extension_point_duration_seconds",
    "generated_placements_total",
    "get_node_hint_duration_seconds",
    "goroutines",
    "inflight_events",
    "pending_async_api_calls",
    "pending_pods",
    "permit_wait_duration_seconds",
    "placement_evaluation_duration_seconds",
    "placement_evaluations_total",
    "plugin_evaluation_total",
    "plugin_execution_duration_seconds",
    "pod_scheduled_after_flush_total",
    "pod_scheduling_attempts",
    "pod_scheduling_sli_duration_seconds",
    "podgroup_schedule_attempts_total",
    "podgroup_scheduling_algorithm_duration_seconds",
    "podgroup_scheduling_attempt_duration_seconds",
    "preemption_attempts_total",
    "preemption_evaluation_duration_seconds",
    "preemption_execution_duration_seconds",
    "preemption_goroutines_duration_seconds",
    "preemption_goroutines_execution_total",
    "preemption_pdb_violations_total",
    "preemption_victims",
    "preemption_workload_disruptions",
    "queue_incoming_entities_total",
    "queue_incoming_pods_total",
    "queued_entities",
    "queueing_hint_execution_duration_seconds",
    "schedule_attempts_total",
    "scheduling_algorithm_duration_seconds",
    "scheduling_attempt_duration_seconds",
    "store_schedule_results_duration_seconds",
    "unschedulable_pods",
    "workload_preemption_attempts_total",
    "workload_preemption_victims",
}


def test_metric_name_parity_with_reference():
    """The registered series names cover the reference scheduler's full set
    (metrics/metrics.go:265-615) — the round-4 VERDICT's metrics sweep."""
    from kubernetes_tpu.core.metrics import SchedulerMetrics

    m = SchedulerMetrics()
    registered = {metric.name for metric in m.registry._metrics}
    expected = {f"scheduler_{n}" for n in REFERENCE_SERIES}
    missing = expected - registered
    assert not missing, f"missing reference series: {sorted(missing)}"
    extra = registered - expected
    # Our additions beyond the reference set (device-path + resilience
    # series, docs/RESILIENCE.md; shard-plane series, docs/SHARDING.md).
    assert extra <= {"scheduler_batch_size",
                     "scheduler_e2e_scheduling_duration_seconds",
                     "scheduler_pod_stage_duration_seconds",
                     "scheduler_loop_stage_seconds_total",
                     "scheduler_loop_stages_total",
                     "scheduler_podgroup_generated_placements",
                     "scheduler_async_api_call_retries_total",
                     "scheduler_device_path_fallback_total",
                     "scheduler_device_path_breaker_open",
                     "scheduler_plan_rebuild_total",
                     "scheduler_plan_rebuild_dirty_rows_total",
                     "scheduler_plan_rebuild_cause_total",
                     "scheduler_plan_ipa_terms_total",
                     "scheduler_plan_anti_lane_total",
                     "scheduler_device_batches_total",
                     "scheduler_device_scan_steps_total",
                     "scheduler_commit_pods_total",
                     "scheduler_queue_popped_pods_total",
                     "scheduler_hint_cache_hits_total",
                     "scheduler_hint_cache_misses_total",
                     "scheduler_hint_cache_invalidations_total",
                     "scheduler_hint_sibling_absorbed_total",
                     "scheduler_hint_validation_duration_seconds",
                     "scheduler_bind_conflict_total",
                     "scheduler_bind_requests_total",
                     "scheduler_bind_request_pods_total",
                     "scheduler_inbox_oldest_wait_seconds",
                     "scheduler_cluster_event_wait_seconds",
                     "scheduler_nominated_evaluations_total",
                     "scheduler_preemption_dry_runs_total",
                     "scheduler_preemption_victim_rows_total",
                     "scheduler_preemptor_plan_total",
                     "scheduler_prefilter_narrowed_pods_total",
                     "scheduler_host_to_device_transfers_total",
                     "scheduler_plan_node_shapes",
                     "scheduler_mirror_rows_total",
                     "scheduler_shard_owned_shards",
                     "scheduler_shard_lease_renewals_total",
                     "scheduler_shard_adoptions_total",
                     "scheduler_watch_decoded_events",
                     "scheduler_watch_decoded_bytes",
                     "scheduler_queue_starvation_seconds"}, extra


def test_new_series_populate_during_scheduling():
    """A mixed run moves the newly wired series (not just registers them)."""
    from kubernetes_tpu.core import FakeClientset, Scheduler
    from kubernetes_tpu.testing import make_node, make_pod

    cs = FakeClientset()
    s = Scheduler(clientset=cs)
    for i in range(4):
        cs.create_node(make_node().name(f"n{i}")
                       .capacity({"cpu": "4", "pods": 10}).obj())
    for i in range(6):
        cs.create_pod(make_pod().name(f"p{i}").req({"cpu": "1"}).obj())
    s.run_until_idle()
    m = s.metrics
    assert m.scheduling_algorithm_duration.count() == 6
    assert m.pod_scheduling_attempts.count() == 6
    assert m.event_handling_duration.count("pod") >= 6
    assert m.event_handling_duration.count("node") == 4
    # preemption moves the preemption series
    cs.create_pod(make_pod().name("hi").req({"cpu": "4"}).priority(100).obj())
    s.run_until_idle()
    for _ in range(20):
        s.process_async_api_errors()
        s.run_until_idle()
    assert m.preemption_evaluation_duration.count() >= 1
    assert m.preemption_execution_duration.count() >= 1
    assert m.preemption_goroutines_execution_total.value("success") >= 1
    # exposure includes callback gauges without error
    text = s.expose_metrics()
    assert "scheduler_inflight_events" in text
    assert "scheduler_queued_entities" in text


def test_metrics_resources_endpoint():
    from kubernetes_tpu.core import FakeClientset, Scheduler
    from kubernetes_tpu.core.server import SchedulerServer
    from kubernetes_tpu.testing import make_node, make_pod
    from urllib.request import urlopen

    cs = FakeClientset()
    s = Scheduler(clientset=cs)
    cs.create_node(make_node().name("n0").capacity(
        {"cpu": "4", "memory": "8Gi", "pods": 10}).obj())
    cs.create_pod(make_pod().name("p0").req({"cpu": "500m", "memory": "1Gi"}).obj())
    s.run_until_idle()
    srv = SchedulerServer(s)
    port = srv.serve(0)
    body = urlopen(f"http://127.0.0.1:{port}/metrics/resources", timeout=5).read().decode()
    srv.shutdown()
    assert "kube_pod_resource_request" in body
    assert 'resource="cpu"' in body and 'phase="Running"' in body
