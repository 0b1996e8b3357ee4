"""What may choose a path on the device pipeline, and what may not.

Which engine places a batch is read from the batch (ops/kernel.py `coupling`,
tests/test_dispatch_cache.py). These guards keep the other ways of
choosing out: no module of the device pipeline reads the environment, and
the schedulers read no option that `SchedulerConfiguration` does not define
(a `getattr(self.config, name, default)` is an option nobody can set). The
collector policy (core/collector.py) is held to the same: constants beside it,
no environment variable, no configuration field, no constructor argument.
"""

import ast
import dataclasses
import inspect

import pytest

from kubernetes_tpu.analysis.base import PKG_ROOT, iter_sources
from kubernetes_tpu.core.config import SchedulerConfiguration


def _modules(*prefixes):
    """The package's parsed sources under the given relative paths."""
    return [m for m in iter_sources(PKG_ROOT) if m.path.startswith(prefixes)]


@pytest.mark.parametrize("package", ["ops/", "models/", "parallel/",
                                     "core/collector.py"])
def test_the_device_pipeline_reads_no_environment_variable(package):
    found = []
    for mod in _modules(package):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Attribute) and node.attr in (
                    "environ", "getenv"):
                found.append(f"{mod.path}:{node.lineno}")
            elif isinstance(node, ast.ImportFrom) and node.module == "os" \
                    and {a.name for a in node.names} & {"environ", "getenv"}:
                found.append(f"{mod.path}:{node.lineno}")
    assert _modules(package), f"no source under {package}"
    assert not found, (
        "a path on the device pipeline (and the collector policy) is chosen "
        f"from what the code can observe, not from the environment: {found}")


def _is_self_config(node) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "config"
            and isinstance(node.value, ast.Name) and node.value.id == "self")


def test_the_schedulers_read_only_options_the_configuration_defines():
    defined = {f.name for f in dataclasses.fields(SchedulerConfiguration)}
    defined |= {name for name in vars(SchedulerConfiguration)
                if not name.startswith("_")}  # its properties and methods
    read, guessed = {}, []
    for mod in _modules("models/", "core/scheduler.py"):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Attribute) and _is_self_config(node.value):
                read.setdefault(node.attr, f"{mod.path}:{node.lineno}")
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Name)
                  and node.func.id == "getattr" and node.args
                  and _is_self_config(node.args[0])):
                guessed.append(f"{mod.path}:{node.lineno}")
    assert read, "no self.config read found: the guard reads nothing"
    assert not guessed, f"getattr(self.config, ...) with a default: {guessed}"
    unknown = {name: where for name, where in read.items()
               if name not in defined}
    assert not unknown, (
        f"read from self.config but not a SchedulerConfiguration field: "
        f"{unknown}")


# What could be set when the collector policy came (PR 30): it added nothing.
CONFIGURATION_FIELDS = {
    "profiles", "percentage_of_nodes_to_score", "pod_initial_backoff_seconds",
    "pod_max_backoff_seconds", "feature_gates", "max_batch", "extenders",
    "async_dispatch_threads", "fair_tenant_dequeue", "tenant_weights"}
SCHEDULER_ARGUMENTS = {
    "clientset", "profile_factory", "percentage_of_nodes_to_score", "seed",
    "deterministic_ties", "config", "now"}


def test_the_collector_policy_adds_no_configuration_field():
    fields = {f.name for f in dataclasses.fields(SchedulerConfiguration)}
    assert fields == CONFIGURATION_FIELDS, (
        "a new field of SchedulerConfiguration: if it steers the collector, "
        "make it a constant in core/collector.py; if not, add it here")


def test_the_collector_policy_takes_no_constructor_argument():
    from kubernetes_tpu.core import collector
    from kubernetes_tpu.core.scheduler import Scheduler
    from kubernetes_tpu.models.tpu_scheduler import TPUScheduler

    def arguments(fn):
        return set(inspect.signature(fn).parameters) - {"self"}

    assert arguments(collector.CollectorPolicy.__init__) == set()
    assert arguments(Scheduler.__init__) == SCHEDULER_ARGUMENTS
    assert arguments(TPUScheduler.__init__) == {
        "args", "kwargs", "max_batch", "mesh"}
    # one instance a process, and every scheduler holds that one
    assert isinstance(collector.POLICY, collector.CollectorPolicy)
