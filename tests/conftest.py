"""Test configuration: tier-1 runs on the CPU by design — a virtual 8-device
CPU mesh, so the sharding tests need no accelerator. What the chip does is
established by chip_smoke.py on the chip, not here."""

import os
import sys

# The CPU is wanted here by name: the env var covers every subprocess the
# suites spawn, the config API pins this process even if the caller's
# environment named another platform.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubernetes_tpu import compile_cache  # noqa: E402

# Persistent XLA compilation cache: the equivalence/fuzz suites compile many
# distinct kernel static-combos (each ~0.5–5 s of backend_compile on a small
# CPU box), and every pytest process — plus every SUBPROCESS the chaos and
# shard-plane tests spawn — used to pay them all again. The cache is keyed
# on HLO+flags+compiler version, so hits are exact; a cold cache only costs
# the first run. Placement is compile_cache.py's one rule; exporting it
# BEFORE jax is imported makes this process and every child read the same
# directory from the environment.
compile_cache.export(os.environ)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)


def pytest_configure(config):
    # Deterministic-seed fault-injection tests (tests/test_faults.py) run in
    # tier-1 under `chaos`; long kill/restart stress rides `slow` and is
    # excluded by the tier-1 `-m 'not slow'` selection.
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection tests (tier-1)")
    config.addinivalue_line(
        "markers", "slow: long-running stress tests (excluded from tier-1)")
