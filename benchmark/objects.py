"""From a configuration file and a seed to what the program is handed.

With the files under ``object_features/``, the only code of the benchmark
that builds the program's own object types (``kubernetes_tpu.testing``
builders, the apiserver's wire dicts). The plain descriptions it starts from
are the same ones ``reference.py`` reads, so the two sides see the same
cluster without sharing any code. It builds the core's pod keys
(``reference.CORE_POD_KEYS``) itself and hands every further key to
``object_features/<key>.py`` (``apply(builder, value, template) -> builder``),
the builder's half of the pod feature that ``reference_features/<key>.py``
states; a key without both files is refused (``features.py``).

``--seed`` changes the order in which the nodes are created (names keep their
zone): the node tree, every tie-break and the rotating start index then see
another cluster, while sizes and shapes stay the configuration's.

A configuration's ``nodes`` is one group (``count``, ``template``) or a list
of groups, each of its own size; a group of one may give its node a ``name``.
Nodes are numbered ``node-<i>`` across the groups and the seed permutes the
whole creation order (``reference.group_descriptions``). ``rehearse`` then
gives the toy counts of ``nodes`` as a list, one for each group.
"""

from __future__ import annotations

import json
from typing import List, Optional

import numpy as np

import features
import reference


def load_config(path: str, rehearse: bool) -> dict:
    with open(path) as f:
        cfg = json.load(f)
    if rehearse:
        # Toy counts for the CPU rehearsal, stated in the file itself.
        for group, count in cfg["rehearse"].items():
            if isinstance(cfg[group], list):
                if len(count) != len(cfg[group]):
                    raise ValueError(f"{path}: rehearse gives {len(count)} "
                                     f"counts for {len(cfg[group])} {group}")
                for g, c in zip(cfg[group], count):
                    g["count"] = int(c)
            else:
                cfg[group]["count"] = int(count)
    return cfg


def groups(cfg: dict, key: str) -> List[dict]:
    """``cfg[key]`` as a list of groups (``count``, ``template``): one group
    or several, none where the key is absent."""
    found = cfg.get(key, [])
    return found if isinstance(found, list) else [found]


def node_groups(cfg: dict) -> List[dict]:
    return groups(cfg, "nodes")


def node_order(count: int, seed: int) -> List[int]:
    return [int(i) for i in
            np.random.default_rng([int(seed), 1]).permutation(count)]


def cluster(cfg: dict, seed: int) -> List[dict]:
    """Plain node descriptions in creation order."""
    groups = node_groups(cfg)
    n = sum(int(g["count"]) for g in groups)
    return reference.group_descriptions(groups, node_order(n, seed))


def make_node(desc: dict):
    from kubernetes_tpu.testing import make_node as builder
    return (builder().name(desc["name"])
            .capacity({"cpu": f"{desc['cpu']}m", "memory": desc["memory"],
                       "pods": desc["pods"]})
            .zone(desc["zone"]).obj())


def make_pod_prototype(template: dict, bench_dir: Optional[str] = None):
    """One pod of the template; stamp the rest with
    ``proto.clone_from_template(name)`` as the program's own perf harness
    does, so that creating a wave costs the client what it costs there.
    Feature files are looked for under ``bench_dir`` (the run's
    ``--bench-dir``) ahead of this file's directory."""
    from kubernetes_tpu.testing import make_pod as builder
    b = builder().name("prototype").req(
        {k: template[k] for k in ("cpu", "memory") if k in template})
    for k, v in template.get("labels", {}).items():
        b = b.label(k, v)
    for key in template:
        if key in reference.CORE_POD_KEYS:
            continue
        feature = features.load("objects", key, bench_dir)
        if feature is None:
            raise reference.Unmodelled(f"pod template key {key!r}")
        b = feature.apply(b, template[key], template)
    return b.obj()


def stamp(proto, name: str):
    """A pod named ``name`` whose uid is its name, so that placements can be
    compared by name on every path (the HTTP wire keys pods by uid)."""
    pod = proto.clone_from_template(name)
    pod.uid = name
    return pod
