"""What may choose a path on the device pipeline, and what may not.

Which engine places a batch is read from the batch (ops/kernel.py `coupling`,
tests/test_dispatch_cache.py). These two guards keep the other ways of
choosing out: no module of the device pipeline reads the environment, and
the schedulers read no option that `SchedulerConfiguration` does not define
(a `getattr(self.config, name, default)` is an option nobody can set).
"""

import ast
import dataclasses

import pytest

from kubernetes_tpu.analysis.base import PKG_ROOT, iter_sources
from kubernetes_tpu.core.config import SchedulerConfiguration


def _modules(*prefixes):
    """The package's parsed sources under the given relative paths."""
    return [m for m in iter_sources(PKG_ROOT) if m.path.startswith(prefixes)]


@pytest.mark.parametrize("package", ["ops", "models", "parallel"])
def test_the_device_pipeline_reads_no_environment_variable(package):
    found = []
    for mod in _modules(package + "/"):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Attribute) and node.attr in (
                    "environ", "getenv"):
                found.append(f"{mod.path}:{node.lineno}")
            elif isinstance(node, ast.ImportFrom) and node.module == "os" \
                    and {a.name for a in node.names} & {"environ", "getenv"}:
                found.append(f"{mod.path}:{node.lineno}")
    assert not found, (
        "a path on the device pipeline is chosen from what the code can "
        f"observe, not from the environment: {found}")


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
