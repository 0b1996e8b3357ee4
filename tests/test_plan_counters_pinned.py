"""The plan counters on one fixed scenario, value for value.

Two plain templates taking turns, a node update and a pod delete between
their sessions, a preemptor that is nominated and retried, gangs, a node
added: every way a plan is acquired, kept, resumed, patched or dropped, on
one scheduler. What is asserted is every series that says how a plan was
come by, and the stage table's counts of the device path. The expected
numbers were read off the parent commit of PR 46 (4d6ecd4), which merged the
session's resume slot and the preemptors' kept plans into one keeper and
gave both session ladders one frame: that change may move none of them, and
nothing here is timed."""

import pytest

from kubernetes_tpu.api.types import Namespace, PodGroup, Taint
from kubernetes_tpu.core.node_info import PodInfo
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.testing.annotations import StageAnnotations
from kubernetes_tpu.testing.wrappers import make_node, make_pod

_STAGES = ("plan.build", "plan.adopt", "plan.patch", "device.dispatch",
           "device.wait")


def _node(name, cpu, taints=()):
    node = make_node().name(name).capacity(
        {"cpu": cpu, "memory": "32Gi", "pods": 110}).zone("zone-0").obj()
    node.taints = list(taints)
    return node


def _pod(name, cpu, priority=0, on=None):
    pod = (make_pod().name(name).uid(name)
           .req({"cpu": cpu, "memory": "100Mi"}).priority(priority).obj())
    if on is not None:
        pod.node_name = on
    return pod


def _scenario(mesh, hints):
    sched = TPUScheduler(max_batch=4, mesh=mesh)
    sched.journal.cap = 16      # set before the first record
    sched.stages._annotation = StageAnnotations()
    if not hints:
        sched._hints.enabled = False
        sched._hints.entry = None
    cs = sched.clientset
    # six nodes kept full by low-priority pods (only a preemption makes room
    # for 3 cpu there) and six small ones for the plain templates
    for i in range(6):
        cs.create_node(_node(f"full-{i}", "4"))
    for i in range(6):
        cs.create_node(_node(f"small-{i}", "2"))
    for i in range(6):
        for j in range(4):
            cs.create_pod(_pod(f"init-{i}-{j}", "900m", priority=-10,
                               on=f"full-{i}"))
    sched.run_until_idle()
    protos = {"a": _pod("a", "100m"), "b": _pod("b", "200m")}
    made = [0]

    def wave(stem, n):
        for _ in range(n):
            made[0] += 1
            pod = protos[stem].clone_from_template(f"{stem}-{made[0]}")
            pod.uid = pod.name
            cs.create_pod(pod)
        sched.run_until_idle()

    # (1) two templates taking turns, batches of four
    for stem in "abab":
        wave(stem, 6)
    # (2) the same template twice, nothing between
    wave("b", 3)
    # a namespace while no pod carries a term: nothing to patch
    cs.create_namespace(Namespace(name="early"))
    wave("b", 3)
    # (3) a node update, then a pod delete, each before a wave of the
    # template that ran last
    cs.update_node(_node("small-0", "2", taints=[
        Taint(key="k", value="v", effect="PreferNoSchedule")]))
    wave("b", 3)
    cs.delete_pod(cs.pods["b-7"])
    wave("b", 3)
    # (4) a preemptor: failed attempt, nomination, retry on its own node;
    # then a second one of the same template
    for name in ("pre-0", "pre-1"):
        cs.create_pod(_pod(name, "3", priority=10))
        sched.run_until_idle()
        assert cs.pods[name].node_name.startswith("full-")
    # (5) gangs of the plain template, two creations apart
    for g in range(3):
        cs.create_pod_group(PodGroup(name=f"g{g}", min_count=2))
        for j in range(2):
            pod = protos["a"].clone_from_template(f"gang-{g}-{j}")
            pod.uid = pod.name
            pod.pod_group = f"g{g}"
            cs.create_pod(pod)
        if g != 1:
            sched.run_until_idle()
    # (6) a node added, then both templates again
    cs.create_node(_node("small-new", "2"))
    wave("a", 5)
    wave("b", 5)
    # (7) somebody else's nomination comes and goes between waves of the
    # template that ran last: nothing but the nominated lane is stale
    other = _pod("elsewhere", "1", priority=5)
    other.nominated_node_name = "small-1"
    sched.queue.nominator.add_nominated_pod(PodInfo.of(other), "small-1")
    wave("b", 2)
    sched.queue.nominator.delete_nominated_pod(other)
    wave("b", 2)
    # (8) a preemptor whose nominated node outlives a node added between
    # its nomination and its retry: the retry finds no plan kept
    cs.create_pod(_pod("pre-2", "3", priority=10))
    for _ in range(50):
        if cs.pods["pre-2"].nominated_node_name:
            break
        assert sched.schedule_one()
    cs.create_node(_node("small-late", "2"))
    sched.run_until_idle()
    assert cs.pods["pre-2"].node_name.startswith("full-")
    # (9) a gang that fits nowhere between two that do
    for g, cpu in (("fits-0", "100m"), ("nofit", "16"), ("fits-1", "100m")):
        cs.create_pod_group(PodGroup(name=g, min_count=2))
        for j in range(2):
            pod = _pod(f"{g}-{j}", cpu)
            pod.pod_group = g
            cs.create_pod(pod)
    sched.run_until_idle()
    # (10) more deletes than the journal holds, then a pod with a required
    # term bound by somebody else: no row patch covers either
    wave("a", 2)
    for name in sorted(n for n in cs.pods if n.startswith("b-"))[:20]:
        cs.delete_pod(cs.pods[name])
    wave("a", 2)
    picky = (make_pod().name("picky").uid("picky")
             .req({"cpu": "100m", "memory": "100Mi"}).label("color", "green")
             .pod_affinity("kubernetes.io/hostname", {"color": "green"},
                           anti=True).obj())
    picky.node_name = "small-2"
    cs.create_pod(picky)
    wave("a", 2)
    cs.create_namespace(Namespace(name="late"))     # and now it is read
    wave("a", 2)
    # (11) a dispatch that raises: everything kept from the device goes
    def once(where, fired=[]):
        if where == "dispatch" and not fired:
            fired.append(where)
            raise RuntimeError("injected")
    sched._fault_hook = once
    wave("a", 3)
    sched._fault_hook = None
    wave("a", 3)
    wave("b", 3)
    unbound = sorted(p.name for p in cs.pods.values() if not p.node_name)
    assert unbound == ["nofit-0", "nofit-1"]
    assert sched.device_breaker.consecutive_failures == 0
    return sched


def _series(counter):
    return {"/".join(k): int(v) for k, v in sorted(counter._values.items())
            if v}


def _said(sched, stage, *stats):
    return ["/".join(str(said.get(k, "")) for k in stats)
            for name, said in sched.stages._annotation.opened
            if name == "sched." + stage]


def _read(sched):
    m = sched.metrics
    return {
        # every session's acquisition in order, as its two ends say it
        "plan.build": _said(sched, "plan.build", "kind", "cause"),
        "plan.adopt": _said(sched, "plan.adopt", "kind", "cause"),
        "postfilter.preempt": _said(sched, "postfilter.preempt", "plan"),
        "nominated.eval": _said(sched, "nominated.eval", "plan", "outcome"),
        "rebuilds": (sched.plan_rebuilds_full, sched.plan_rebuilds_delta,
                     sched.plan_rebuilds_resume),
        "plan_rebuild_total": _series(m.plan_rebuild_total),
        "plan_rebuild_cause_total": _series(m.plan_rebuild_cause),
        "preemptor_plan_total": _series(m.preemptor_plans),
        "preemption_victim_rows_total": _series(m.preemption_victim_rows),
        "batch_cache_flushed_total": _series(m.batch_cache_flushed),
        "stages": {s: sched.stages.counts[s] for s in _STAGES},
    }


_EXPECTED = {'sessions': {'plan.build': ['full/first', 'full/other_pod', 'full/other_pod',
                             'full/other_pod', 'resume/', 'delta/',
                             'full/patch_failed', 'delta/', 'full/other_pod',
                             'full/first', 'full/first', 'delta/',
                             'full/structural', 'full/other_pod',
                             'full/nomination', 'full/nomination',
                             'full/other_pod', 'full/first', 'full/other_pod',
                             'full/first', 'resume/', 'full/journal_overrun',
                             'full/other_pod', 'full/first',
                             'full/unpatchable', 'resume/', 'full/first',
                             'full/other_pod'],
              'plan.adopt': ['full/first', 'full/other_pod', 'full/other_pod',
                             'full/other_pod', 'resume/', 'delta/',
                             'full/patch_failed', 'delta/', 'full/other_pod',
                             'full/first', 'full/first', 'delta/',
                             'full/structural', 'full/other_pod',
                             'full/nomination', 'full/nomination',
                             'full/other_pod', 'full/first', 'full/other_pod',
                             'full/first', 'resume/', 'full/journal_overrun',
                             'full/other_pod', 'full/first',
                             'full/unpatchable', 'full/first',
                             'full/other_pod'],
              'postfilter.preempt': ['kept', 'kept', 'kept'],
              'nominated.eval': ['kept/bound', 'kept/bound', 'built/bound'],
              'rebuilds': (22, 3, 3),
              'plan_rebuild_total': {'delta': 3, 'full': 22, 'resume': 3},
              'plan_rebuild_cause_total': {'first': 7,
                                           'journal_overrun': 1,
                                           'nomination': 2,
                                           'other_pod': 9,
                                           'patch_failed': 1,
                                           'structural': 1,
                                           'unpatchable': 1},
              'preemptor_plan_total': {'dry_run/kept': 3,
                                       'nominated/built': 1,
                                       'nominated/kept': 2},
              'preemption_victim_rows_total': {'kept': 17, 'rebuilt': 20},
              'batch_cache_flushed_total': {'device_path_failure': 1,
                                            'gang_session_invalidated': 2,
                                            'session_invalidated': 3},
              'stages': {'plan.build': 28,
                         'plan.adopt': 27,
                         'plan.patch': 2,
                         'device.dispatch': 38,
                         'device.wait': 37}},
 'hints': {'plan.build': ['full/first', 'full/other_pod', 'full/other_pod',
                          'delta/', 'full/other_pod', 'full/first',
                          'full/first', 'delta/', 'full/structural',
                          'full/other_pod', 'full/nomination',
                          'full/nomination', 'full/other_pod', 'full/first',
                          'full/other_pod', 'full/first', 'resume/',
                          'full/journal_overrun', 'full/other_pod',
                          'full/first', 'full/unpatchable', 'resume/',
                          'full/first', 'full/other_pod'],
           'plan.adopt': ['full/first', 'full/other_pod', 'full/other_pod',
                          'delta/', 'full/other_pod', 'full/first',
                          'full/first', 'delta/', 'full/structural',
                          'full/other_pod', 'full/nomination',
                          'full/nomination', 'full/other_pod', 'full/first',
                          'full/other_pod', 'full/first', 'resume/',
                          'full/journal_overrun', 'full/other_pod',
                          'full/first', 'full/unpatchable', 'full/first',
                          'full/other_pod'],
           'postfilter.preempt': ['kept', 'kept', 'kept'],
           'nominated.eval': ['kept/bound', 'kept/bound', 'built/bound'],
           'rebuilds': (20, 2, 2),
           'plan_rebuild_total': {'delta': 2, 'full': 20, 'resume': 2},
           'plan_rebuild_cause_total': {'first': 7,
                                        'journal_overrun': 1,
                                        'nomination': 2,
                                        'other_pod': 8,
                                        'structural': 1,
                                        'unpatchable': 1},
           'preemptor_plan_total': {'dry_run/kept': 3,
                                    'nominated/built': 1,
                                    'nominated/kept': 2},
           'preemption_victim_rows_total': {'kept': 17, 'rebuilt': 20},
           'batch_cache_flushed_total': {'device_path_failure': 1,
                                         'gang_session_invalidated': 2,
                                         'session_invalidated': 3},
           'stages': {'plan.build': 24,
                      'plan.adopt': 23,
                      'plan.patch': 1,
                      'device.dispatch': 32,
                      'device.wait': 31}}}


@pytest.mark.parametrize("hints", (False, True), ids=("sessions", "hints"))
@pytest.mark.parametrize("mesh", (None, "auto"), ids=("single", "mesh"))
def test_the_plan_counters_read_as_at_the_parent(mesh, hints):
    got = _read(_scenario(mesh, hints))
    plane = "mesh" if mesh else "single"
    # one plane a scheduler: the label is the only thing the two differ by
    assert all(k.endswith("/" + plane) for k in got["plan_rebuild_total"])
    got["plan_rebuild_total"] = {
        k.split("/")[0]: v for k, v in got["plan_rebuild_total"].items()}
    want = _EXPECTED["hints" if hints else "sessions"]
    for name in want:
        assert got[name] == want[name], name
    assert got.keys() == want.keys()
