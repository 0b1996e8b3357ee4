"""DynamicResources (DRA) plugin: structured-parameter claim allocation
(reference plugins/dynamicresources/)."""

from kubernetes_tpu.api.dra import (
    Device,
    DeviceClass,
    DeviceRequest,
    ResourceClaim,
    ResourceSlice,
)
from kubernetes_tpu.core.config import PluginSet, ProfileConfig, SchedulerConfiguration
from kubernetes_tpu.core.scheduler import Scheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod


def _dra_sched():
    cfg = SchedulerConfiguration(profiles=[ProfileConfig(
        plugins=PluginSet(enabled=(("DynamicResources", 0),)))])
    return Scheduler(config=cfg, deterministic_ties=True)


def _gpu_node(s, name, n_gpus, gpu_type="a100"):
    s.clientset.create_node(
        make_node().name(name).capacity({"cpu": "16", "pods": 20}).obj())
    s.clientset.create_resource_slice(ResourceSlice(
        node_name=name, driver="gpu.example.com",
        devices=[Device(name=f"{name}-gpu{i}", attributes={"type": gpu_type})
                 for i in range(n_gpus)]))


def _claim_pod(s, pod_name, claim_name, count=1, selectors=None, device_class=""):
    s.clientset.create_resource_claim(ResourceClaim(
        name=claim_name,
        requests=[DeviceRequest(count=count, selectors=selectors or {},
                                device_class=device_class)]))
    p = make_pod().name(pod_name).req({"cpu": "1"}).obj()
    p.resource_claims.append(claim_name)
    s.clientset.create_pod(p)
    return p


class TestDynamicResources:
    def test_allocates_devices_on_fitting_node(self):
        s = _dra_sched()
        _gpu_node(s, "cpu-only", 0)
        _gpu_node(s, "gpu-node", 2)
        _claim_pod(s, "p", "claim-a", count=2)
        s.run_until_idle()
        assert list(s.clientset.bindings.values()) == ["gpu-node"]
        claim = s.clientset.resource_claims["default/claim-a"]
        assert claim.allocated_node == "gpu-node"
        assert len(claim.allocations) == 2
        assert claim.reserved_for  # pod recorded

    def test_devices_are_exclusive(self):
        s = _dra_sched()
        _gpu_node(s, "gpu-node", 1)
        _claim_pod(s, "p1", "c1", count=1)
        _claim_pod(s, "p2", "c2", count=1)
        s.run_until_idle()
        assert s.scheduled == 1  # second claim can't get the only GPU

    def test_selector_matching(self):
        s = _dra_sched()
        _gpu_node(s, "a100-node", 1, gpu_type="a100")
        _gpu_node(s, "h100-node", 1, gpu_type="h100")
        _claim_pod(s, "p", "c", selectors={"type": "h100"})
        s.run_until_idle()
        assert list(s.clientset.bindings.values()) == ["h100-node"]

    def test_device_class_selectors(self):
        s = _dra_sched()
        s.clientset.create_device_class(DeviceClass(
            name="big-gpu", selectors={"type": "h100"}))
        _gpu_node(s, "small", 4, gpu_type="a100")
        _gpu_node(s, "big", 1, gpu_type="h100")
        _claim_pod(s, "p", "c", device_class="big-gpu")
        s.run_until_idle()
        assert list(s.clientset.bindings.values()) == ["big"]

    def test_preallocated_claim_pins_node(self):
        s = _dra_sched()
        _gpu_node(s, "n0", 1)
        _gpu_node(s, "n1", 1)
        claim = ResourceClaim(name="pinned", requests=[DeviceRequest(count=1)])
        claim.allocated_node = "n1"
        s.clientset.create_resource_claim(claim)
        p = make_pod().name("p").req({"cpu": "1"}).obj()
        p.resource_claims.append("pinned")
        s.clientset.create_pod(p)
        s.run_until_idle()
        assert list(s.clientset.bindings.values()) == ["n1"]

    def test_missing_claim_unresolvable(self):
        s = _dra_sched()
        _gpu_node(s, "n0", 1)
        p = make_pod().name("p").req({"cpu": "1"}).obj()
        p.resource_claims.append("no-such-claim")
        s.clientset.create_pod(p)
        s.run_until_idle()
        assert s.scheduled == 0


class TestExpressionSelectors:
    """Structured parameters with CEL-equivalent device selector expressions
    (staging dynamic-resource-allocation/cel; DeviceSelector.cel.expression)."""

    def _cluster(self):
        from kubernetes_tpu.api.dra import Device, ResourceSlice
        from kubernetes_tpu.testing.wrappers import make_node
        s = _dra_sched()
        cs = s.clientset
        for i in range(4):
            cs.create_node(make_node().name(f"n{i}").capacity(
                {"cpu": 8, "memory": "32Gi", "pods": 110}).obj())
            model = "a100" if i % 2 == 0 else "t4"
            cs.create_resource_slice(ResourceSlice(
                node_name=f"n{i}", driver="gpu.example.com",
                devices=[Device(name=f"gpu-{i}-{j}",
                                attributes={"model": model, "mem": "40" if model == "a100" else "16"})
                         for j in range(2)]))
        return cs, s

    def test_expression_picks_matching_devices(self):
        from kubernetes_tpu.api.dra import DeviceRequest, ResourceClaim
        from kubernetes_tpu.testing.wrappers import make_pod
        cs, s = self._cluster()
        claim = ResourceClaim(name="big-gpu", requests=[DeviceRequest(
            name="gpu", count=1,
            expression='device.attributes["model"] == "a100" and device.attributes["mem"] >= 32')])
        cs.create_resource_claim(claim)
        p = make_pod().name("train").req({"cpu": "1"}).obj()
        p.resource_claims = ["big-gpu"]
        cs.create_pod(p)
        s.run_until_idle()
        assert p.node_name in ("n0", "n2"), p.node_name  # a100 nodes only
        assert claim.allocated and claim.allocated_node == p.node_name

    def test_expression_no_match_unschedulable(self):
        from kubernetes_tpu.api.dra import DeviceRequest, ResourceClaim
        from kubernetes_tpu.testing.wrappers import make_pod
        cs, s = self._cluster()
        claim = ResourceClaim(name="h100", requests=[DeviceRequest(
            name="gpu", count=1,
            expression='device.attributes["model"] == "h100"')])
        cs.create_resource_claim(claim)
        p = make_pod().name("train").req({"cpu": "1"}).obj()
        p.resource_claims = ["h100"]
        cs.create_pod(p)
        s.run_until_idle()
        assert not p.node_name and s.failures >= 1

    def test_alloc_claims_opcode_respects_expressions(self):
        from kubernetes_tpu.api.dra import DeviceRequest, ResourceClaim
        from kubernetes_tpu.plugins.dynamicresources import allocate_pending_claims
        cs, s = self._cluster()
        for i in range(3):
            cs.create_resource_claim(ResourceClaim(
                name=f"c{i}", requests=[DeviceRequest(
                    name="gpu", count=1,
                    expression='device.attributes["model"] == "t4"')]))
        n = allocate_pending_claims(cs)
        assert n == 3
        nodes = {cs.resource_claims[f"default/c{i}"].allocated_node for i in range(3)}
        assert nodes <= {"n1", "n3"}

    def test_disallowed_expression_rejected(self):
        import pytest
        from kubernetes_tpu.api.dra import ExpressionError, compile_device_expression
        for bad in ('__import__("os").system("true")', 'open("/etc/passwd")',
                    'device.__class__', 'x + 1'):
            with pytest.raises(ExpressionError):
                compile_device_expression(bad)


def _dra_sched_pair(**kw):
    from kubernetes_tpu.core.clientset import FakeClientset
    from kubernetes_tpu.core.config import SchedulerConfiguration
    from kubernetes_tpu.core.registry import DEFAULT_PLUGINS, build_framework
    from kubernetes_tpu.core.scheduler import Scheduler

    cs = FakeClientset()
    plugins = DEFAULT_PLUGINS + (("DynamicResources", 0),)
    cfg = SchedulerConfiguration(feature_gates={
        "DynamicResourceAllocation": True,
        "DRAExtendedResource": True,
        "DRANodeAllocatableResources": True,
    })
    sched = Scheduler(clientset=cs, deterministic_ties=True, config=cfg,
                      profile_factory=lambda h: {
                          "default-scheduler": build_framework(h, plugins=plugins)},
                      **kw)
    return cs, sched


def test_extended_resources_backed_by_dra():
    """extendeddynamicresources.go: a pod requesting example.com/gpu with a
    mapping DeviceClass allocates DRA devices on a node with no device
    plugin capacity; the special in-memory claim becomes a real object at
    PreBind with the pod recorded in reservedFor."""
    from kubernetes_tpu.api.dra import Device, DeviceClass, ResourceSlice
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    cs, sched = _dra_sched_pair()
    cs.create_node(make_node().name("n0").capacity({"cpu": "8", "pods": 10}).obj())
    cs.create_resource_slice(ResourceSlice(
        node_name="n0", driver="gpu.example.com",
        devices=[Device(name=f"gpu-{i}") for i in range(4)]))
    cs.create_device_class(DeviceClass(
        name="gpus", extended_resource_name="example.com/gpu"))
    pod = make_pod().name("p").req({"cpu": "1", "example.com/gpu": 2}).obj()
    cs.create_pod(pod)
    sched.run_until_idle()
    assert cs.bindings.get(pod.uid) == "n0"
    claim = cs.resource_claims.get("default/p-extended-resources")
    assert claim is not None
    assert claim.allocated_node == "n0"
    assert len(claim.allocations) == 2
    assert pod.uid in claim.reserved_for
    assert pod.extended_resource_claim_status["claim"] == claim.key


def test_extended_resources_satisfied_by_device_plugin():
    """When the node's device plugin already advertises the extended
    resource, no DRA allocation happens (filterExtendedResources split)."""
    from kubernetes_tpu.api.dra import DeviceClass
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    cs, sched = _dra_sched_pair()
    cs.create_node(make_node().name("n0").capacity(
        {"cpu": "8", "pods": 10, "example.com/gpu": 4}).obj())
    cs.create_device_class(DeviceClass(
        name="gpus", extended_resource_name="example.com/gpu"))
    pod = make_pod().name("p").req({"cpu": "1", "example.com/gpu": 2}).obj()
    cs.create_pod(pod)
    sched.run_until_idle()
    assert cs.bindings.get(pod.uid) == "n0"
    assert cs.resource_claims.get("default/p-extended-resources") is None


def test_dra_device_node_allocatable_consumption():
    """nodeallocatabledynamicresources.go: an allocated device's declared
    node-resource consumption counts against the node's allocatable."""
    from kubernetes_tpu.api.dra import Device, DeviceRequest, ResourceClaim, ResourceSlice
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    cs, sched = _dra_sched_pair()
    cs.create_node(make_node().name("n0").capacity({"cpu": "4", "pods": 10}).obj())
    cs.create_resource_slice(ResourceSlice(
        node_name="n0", driver="x.csi",
        devices=[Device(name="d0", consumes={"cpu": "3"})]))
    # pod requests 2 cpu; device consumes 3 more => 5 > 4 allocatable
    cs.create_resource_claim(ResourceClaim(
        name="c", requests=[DeviceRequest(name="r", count=1)]))
    pod = make_pod().name("p").req({"cpu": "2"}).obj()
    pod.resource_claims = ["c"]
    cs.create_pod(pod)
    sched.run_until_idle()
    assert cs.bindings.get(pod.uid) is None

    # a lighter pod fits alongside the device's consumption
    cs.create_resource_claim(ResourceClaim(
        name="c2", requests=[DeviceRequest(name="r", count=1)]))
    pod2 = make_pod().name("p2").req({"cpu": "1"}).obj()
    pod2.resource_claims = ["c2"]
    cs.create_pod(pod2)
    sched.run_until_idle()
    assert cs.bindings.get(pod2.uid) == "n0"


def test_typed_capacity_expression():
    """Typed CEL capacity semantics: quantity strings compare numerically
    (device.capacity["memory"] >= 40Gi-in-bytes for "80Gi")."""
    from kubernetes_tpu.api.dra import Device, compile_device_expression

    m = compile_device_expression(
        'device.capacity["memory"] >= 42949672960')
    assert m(Device(name="d", capacity={"memory": "80Gi"}), "drv")
    assert not m(Device(name="d", capacity={"memory": "16Gi"}), "drv")


def test_claim_template_pods_ride_device_and_match_host():
    """Claim-template pods (one unallocated single-request claim each):
    the kernel models free matching devices as the counted aux resource;
    the host commit allocates on the chosen node — assignments AND device
    exhaustion behavior identical to the host oracle."""
    from kubernetes_tpu.api.dra import Device, DeviceRequest, ResourceClaim, ResourceSlice
    from kubernetes_tpu.core.clientset import FakeClientset
    from kubernetes_tpu.core.registry import DEFAULT_PLUGINS, build_framework
    from kubernetes_tpu.core.scheduler import Scheduler
    from kubernetes_tpu.models import TPUScheduler
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    def run(cls):
        cs = FakeClientset()
        plugins = DEFAULT_PLUGINS + (("DynamicResources", 0),)
        kw = {"deterministic_ties": True} if cls is Scheduler else {}
        sched = cls(clientset=cs, profile_factory=lambda h: {
            "default-scheduler": build_framework(h, plugins=plugins)}, **kw)
        for i in range(8):
            cs.create_node(make_node().name(f"n{i}")
                           .capacity({"cpu": "32", "pods": 110}).obj())
            cs.create_resource_slice(ResourceSlice(
                node_name=f"n{i}", driver="gpu.x",
                devices=[Device(name=f"n{i}-d{j}",
                                attributes={"model": "a100" if j < 2 else "v100"})
                         for j in range(4)]))
        pods = []
        # 20 pods x 1 matching device; only 16 matching devices exist
        for i in range(20):
            cs.create_resource_claim(ResourceClaim(
                name=f"c{i}", requests=[DeviceRequest(
                    name="r", count=1,
                    expression='device.attributes["model"] == "a100"')]))
            p = make_pod().name(f"p{i}").req({"cpu": "100m"}).obj()
            p.resource_claims = [f"c{i}"]
            cs.create_pod(p)
            pods.append(p)
        sched.run_until_idle()
        return cs, sched, pods

    cs_h, host, ph = run(Scheduler)
    cs_d, dev, pd = run(TPUScheduler)
    hb = {p.name: cs_h.bindings.get(p.uid) for p in ph}
    db = {p.name: cs_d.bindings.get(p.uid) for p in pd}
    assert hb == db
    assert sum(1 for v in db.values() if v) == 16  # device pool exhausted
    assert dev.device_scheduled >= 14
    # committed claims carry real allocations on the bound node
    for p in pd:
        node = cs_d.bindings.get(p.uid)
        claim = cs_d.resource_claims[f"default/{p.resource_claims[0]}"]
        if node:
            assert claim.allocated_node == node
            assert len(claim.allocations) == 1
            assert p.uid in claim.reserved_for
        else:
            assert not claim.allocated


def test_quantity_string_equality_in_expressions():
    """Typed quantities compare against the ORIGINAL suffixed string form
    too: coercion to numbers must not silently break
    device.capacity["x"] == "40Gi" (round-4 advisor finding)."""
    from kubernetes_tpu.api.dra import Device, compile_device_expression

    d = Device(name="d", capacity={"memory": "40Gi"},
               attributes={"model": "a100", "count": "8"})
    assert compile_device_expression(
        'device.capacity["memory"] == "40Gi"')(d, "drv")
    assert compile_device_expression(
        'device.capacity["memory"] == 42949672960')(d, "drv")
    assert compile_device_expression(
        'device.attributes["count"] == "8"')(d, "drv")
    assert compile_device_expression(
        'device.attributes["count"] >= "4"')(d, "drv")
    assert not compile_device_expression(
        'device.capacity["memory"] == "16Gi"')(d, "drv")
    # non-numeric strings still compare as strings
    assert compile_device_expression(
        'device.attributes["model"] == "a100"')(d, "drv")


def test_quantity_hash_eq_consistency():
    """Regression: coerced quantity values must satisfy the
    hash/eq contract (a == b ⇒ hash(a) == hash(b)) for EVERY pairing of
    coerced, raw-string, and plain-numeric forms — so mixing them in one
    set or dict is well-defined. Cross-type string equality was dropped
    (expression string literals coerce at compile time instead,
    _ConstCoercer; see test_quantity_string_equality_in_expressions)."""
    from kubernetes_tpu.api.dra import _CoercingMap

    q8 = _CoercingMap._coerce("8")
    q25 = _CoercingMap._coerce("2.5")
    qgi = _CoercingMap._coerce("40Gi")
    forms = [q8, "8", 8, q25, 2.5, "2.5", qgi, 40 * 1024 ** 3, "40Gi"]
    for a in forms:
        for b in forms:
            if a == b:
                assert hash(a) == hash(b), (a, b)
    # one set/dict holding BOTH forms: coerced collapses with the number,
    # the raw string stays a distinct, reachable member
    s = {q8, "8", 8}
    assert len(s) == 2 and 8 in s and "8" in s
    d = {q8: "qty", "8": "raw"}
    assert len(d) == 2 and d[8] == "qty" and d["8"] == "raw"
    # ordering against suffixed strings still coerces (no hash contract)
    assert qgi >= "32Gi" and q8 < "16"


def test_const_coercion_scoped_to_quantity_map_comparisons():
    """The compile-time coercion must ONLY touch comparator operands of the
    two quantity maps: subscript KEYS stay literal strings (the map is
    string-keyed), plain-string fields compare as strings, and `in`
    membership against a quantity map coerces tuple members."""
    from kubernetes_tpu.api.dra import Device, compile_device_expression

    d = Device(name="0", attributes={"8": "yes", "count": "8",
                                     "model": "a100"})
    # quantity-shaped SUBSCRIPT KEY: looked up as the string "8"
    assert compile_device_expression(
        'device.attributes["8"] == "yes"')(d, "drv")
    # quantity-shaped literal vs a PLAIN-STRING field: string semantics
    assert compile_device_expression('device.name == "0"')(d, "drv")
    assert not compile_device_expression('device.name == "1"')(d, "drv")
    # membership against a quantity map coerces the tuple members
    assert compile_device_expression(
        'device.attributes["count"] in ("4", "8")')(d, "drv")
    assert compile_device_expression(
        'device.attributes["model"] in ("a100", "h100")')(d, "drv")


def test_coerced_memo_invalidates_on_map_replacement():
    """Replacing a device's attribute/capacity maps (the copy-on-write
    mutation contract) must invalidate the memoized coerced views — stale
    CEL values were the round-4 advisor finding."""
    from kubernetes_tpu.api.dra import Device, compile_device_expression

    d = Device(name="d", attributes={"model": "a100"})
    m = compile_device_expression('device.attributes["model"] == "a100"')
    assert m(d, "drv")
    d.attributes = {"model": "h100"}  # slice update replaces the map
    assert not m(d, "drv")
    assert compile_device_expression(
        'device.attributes["model"] == "h100"')(d, "drv")
