"""The entry points that say what ran on the chip must not be able to lie.

- chip_smoke.py: both stages at toy size in its labelled rehearsal form,
  with the same checks as on the chip (oracle identity, counters, device
  line); without that form, on this CPU-only box, it fails and names the
  missing chip — as it does standing alone without the package;
- `python -m kubernetes_tpu --platform tpu` exits non-zero before any ready
  line when JAX has no TPU;
- `python -m kubernetes_tpu.perf` refuses a backend nobody asked for, labels
  what ran from `jax.devices()`, and fails a run during which the device-path
  breaker was charged — while scheduling still completes on the host path;
- the compile cache goes where JAX_COMPILATION_CACHE_DIR says and nowhere
  else, `<checkout>/.jax_cache` otherwise, and a second process on the same
  shapes adds nothing (the spawned-binary case rides
  test_aux_subsystems.py::test_scheduler_binary_once_mode).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from kubernetes_tpu import compile_cache
from kubernetes_tpu.testing.faults import DeviceFaults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(cmd, env=None, cwd=REPO, timeout=600):
    return subprocess.run(cmd, cwd=cwd, env=env or dict(os.environ),
                          capture_output=True, text=True, timeout=timeout)


# -- chip_smoke.py ----------------------------------------------------------

def test_rehearsal_runs_both_stages_with_the_chip_checks():
    """On four virtual CPU devices this rehearses the four-chip form: a 1x4
    node mesh, shard_map on the plain row, state sharded over all four."""
    out = _run([sys.executable, SMOKE, "--rehearsal"], env=dict(
        os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True, "rehearsal": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    report = json.loads(lines[-2])["report"]
    assert report["ok"] and not report["problems"]
    rows = report["library"]
    assert len(rows) == 3
    for name, rec in rows.items():
        assert rec["scheduled"] == rec["pods_created"] > 0, name
        assert report["compared_pods"][name] == rec["pods_created"], name
        assert rec["device_batches"] > 0 and rec["host_path_pods"] == 0
        assert not any(rec["fallbacks"].values())
        assert rec["mesh"] == {"cells": 1, "nodes": 4}
        assert rec["state_shards"] == {"min_per_array": 4,
                                       "devices": [0, 1, 2, 3]}
        assert rec["first_bound_s"] is not None
        assert "backend_compile_s" in rec["compile"]
    basic = rows["SchedulingBasic/5000Nodes_10000Pods"]
    assert basic["shard_map_dispatches"] > 0
    assert basic["hint_hits"] > 0            # the split is on record
    server = report["server"]
    assert server["device"] == last["device"]  # from the binary's ready line
    assert server["bound"] == server["pods_created"] > 0
    assert report["compared_pods"]["server"] == server["pods_created"]
    assert server["device_batches"] > 0 and server["host_path_pods"] == 0
    assert report["cache"]["dir"] == os.environ[compile_cache.ENV_VAR]


def test_without_a_chip_the_smoke_fails_and_names_it():
    out = _run([sys.executable, SMOKE])
    assert out.returncode != 0
    assert "no TPU" in out.stderr and "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_the_smoke_alone_without_the_package_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run([sys.executable, "chip_smoke.py"], cwd=str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


# -- the scheduler binary ---------------------------------------------------

def test_platform_tpu_exits_before_the_ready_line_without_a_tpu():
    out = _run([sys.executable, "-m", "kubernetes_tpu", "--platform", "tpu",
                "--port", "0", "--once"],
               env=dict(os.environ, PYTHONPATH=REPO))
    assert out.returncode != 0
    assert "serving on" not in out.stdout
    assert "no TPU" in out.stderr


# -- measuring entry points -------------------------------------------------

def test_measuring_device_refuses_a_backend_nobody_asked_for(monkeypatch):
    from kubernetes_tpu.perf.device import measuring_device

    assert measuring_device()["platform"] == "cpu"  # asked for by name
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit, match="no TPU"):
        measuring_device()


def test_perf_table_fails_when_the_breaker_was_charged(monkeypatch, tmp_path):
    from kubernetes_tpu.models import TPUScheduler
    from kubernetes_tpu.perf.__main__ import main

    orig = TPUScheduler.__init__

    def init(self, *a, **kw):
        """Every TPUScheduler fails its first device dispatch (the init
        pods') — once, so the breaker is charged but stays closed."""
        orig(self, *a, **kw)
        self._fault_hook = DeviceFaults(dispatch={1})

    monkeypatch.setattr(TPUScheduler, "__init__", init)
    out = tmp_path / "perf.json"
    rc = main(["--labels", "short", "--scale", "0.1", "--out", str(out),
               "--only", "SchedulingBasic/500Nodes_1000Pods"])
    assert rc == 1
    doc = json.loads(out.read_text())
    assert doc["meta"]["platform"] == "cpu"
    assert doc["meta"]["device"]["count"] == 8
    (row,) = doc["results"]
    assert row["breaker_charged"] == {"RuntimeError": 1}
    assert row["meets_threshold"] is False
    assert row["scheduled"] == 110  # completed on the host path


# -- compile cache placement ------------------------------------------------

_CACHE_CHILD = """
import jax
from kubernetes_tpu.core import FakeClientset
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.testing import make_node, make_pod
cs = FakeClientset()
sched = TPUScheduler(clientset=cs)
for i in range(8):
    cs.create_node(make_node().name(f"n{i}").capacity(
        {"cpu": 8, "memory": "16Gi", "pods": 110}).obj())
for i in range(4):
    cs.create_pod(make_pod().name(f"p{i}").req({"cpu": "500m"}).obj())
sched.run_until_idle()
assert sched.device_scheduled == 4
print(jax.config.jax_compilation_cache_dir)
"""


def _cache_env(cache_dir=None):
    """One device, and every compile cached however fast (CPU compiles sit
    under JAX's default 1 s threshold)."""
    env = dict(os.environ, PYTHONPATH=REPO,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("XLA_FLAGS", None)
    env.pop(compile_cache.ENV_VAR, None)
    if cache_dir is not None:
        env[compile_cache.ENV_VAR] = cache_dir
    return env


def test_cache_goes_where_the_environment_says_and_nowhere_else(tmp_path):
    placed = str(tmp_path / "placed")
    first = _run([sys.executable, "-c", _CACHE_CHILD], env=_cache_env(placed))
    assert first.returncode == 0, first.stderr[-2000:]
    assert first.stdout.strip().splitlines()[-1] == placed
    n1 = compile_cache.entry_count(placed)
    assert n1 > 0
    # a second process on the same shapes hits, and adds nothing
    second = _run([sys.executable, "-c", _CACHE_CHILD],
                  env=_cache_env(placed))
    assert second.returncode == 0, second.stderr[-2000:]
    assert compile_cache.entry_count(placed) == n1


def test_cache_defaults_to_the_checkout(monkeypatch):
    out = _run([sys.executable, "-c", _CACHE_CHILD], env=_cache_env())
    assert out.returncode == 0, out.stderr[-2000:]
    default = os.path.join(REPO, ".jax_cache")
    assert out.stdout.strip().splitlines()[-1] == default
    assert compile_cache.entry_count(default) > 0
    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.cache_dir() == default
