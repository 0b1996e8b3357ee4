"""What PR 35 added to the benchmark: one reader, `commit_batch_share` (the
share of the traced waves' retired batches whose `sched.host.commit` span the
program opened with `tail=batch`), and one entry of `per_layer`. The entry
lists two of the five `.waves` cells, `spread-5k.waves` and
`antiaffinity-5k.waves`: the accepted tests of the other three
(`test_benchmark_basic50k.py`, `test_benchmark_prefaffinity.py`,
`test_benchmark_harness.py`) hold each cell's line to the metrics their PRs
listed it under, and those files are a `benchmark` PR's to edit, not this
one's. The reader on canned observations, beside the tests of
`scan_normalised_share` and `backlog_at_pop_mean`, the manifest held to the
parent's, entry for entry, and traced rehearsals of the three unlisted cells
under a copy of the manifest that lists them, so that the PR that appends
them finds what the reader reads there."""

import hashlib
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for p in (ROOT, BENCH):          # the reader imports its neighbours by name
    if p not in sys.path:
        sys.path.insert(0, p)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
METRIC = "commit_batch_share"
WAVE_CELLS = ["spread-5k.waves", "basic-5k.waves", "antiaffinity-5k.waves",
              "prefaffinity-5k.waves", "basic-5k-50k.waves"]
LISTED_CELLS = ["spread-5k.waves", "antiaffinity-5k.waves"]
# BENCHMARK.json at the parent commit (ae9079e): its sha256 and how many
# entries each list had
PARENT_MANIFEST = "19d84ae3fd9b5bebdd2ed6d86962271a40eac2880d26548f5d07061597c6926a"
PARENT_ENTRIES = {"configs": 5, "workloads": 8, "end_to_end": 4,
                  "per_layer": 28}


def _reader():
    path = os.path.join(BENCH, "layer_metrics", METRIC + ".py")
    spec = importlib.util.spec_from_file_location("under_test_" + METRIC, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("found, waves, want", [
    # with the stat: the commits inside the last `waves` wave spans
    ([[90.0, "single"], [100.0, "batch"], [120.0, "batch"],
      [310.0, "batch"]], 2, 100.0),
    ([[100.0, "batch"], [120.0, "mixed"], [310.0, "single"],
      [320.0, "batch"]], 2, 50.0),
    ([[100.0, "batch"], [310.0, "single"]], 1, 0.0),
    # without it (the parent of PR 35): nothing
    ([[100.0, None], [310.0, None]], 2, None),
    # no commit inside a traced wave (a hint-bound wave), no traced wave
    ([[50.0, "batch"], [160.0, "batch"]], 2, None),
    ([[100.0, "batch"]], 0, None),
])
def test_the_share_of_the_traced_waves_commits_by_the_batch_tail(
        found, waves, want):
    reader = _reader()
    bench = [["bench.init", 0.0, 90.0], ["bench.wave", 100.0, 50.0],
             ["bench.restore", 150.0, 20.0], ["bench.wave", 300.0, 50.0]]
    got = reader.share(bench, found, waves)
    assert got is None if want is None else got == pytest.approx(want)
    # an untraced run, a traced run with no wave traced
    assert reader.read({}) is None
    assert reader.read({"traced": {"waves": 0}}) is None


def test_what_this_pr_appended_to_the_manifest():
    (metric,) = [m for m in MANIFEST["per_layer"] if m["name"] == METRIC]
    assert dict(metric, workloads=None) == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "host scheduler loop",
        "moves": "pods_per_s", "workloads": None}
    assert metric["workloads"][:len(LISTED_CELLS)] == LISTED_CELLS
    reports = {w for e in MANIFEST["end_to_end"] if e["name"] == "pods_per_s"
               for w in e["workloads"]}
    assert set(metric["workloads"]) <= reports
    # the cells of the in-process driver, whose dispatcher is inline: the
    # served cells' scheduler binds through the worker and never engages
    by_name = {w["name"]: w for w in MANIFEST["workloads"]}
    assert all(by_name[w]["traffic"].startswith("waves") for w in WAVE_CELLS)
    # the parent's entries are a prefix of every list: cut back to them, the
    # file is the parent's, byte for byte (one entry appended, nothing else)
    m = json.loads(json.dumps(MANIFEST))
    for key, n in PARENT_ENTRIES.items():
        assert len(m[key]) >= n
        m[key] = m[key][:n]
    assert MANIFEST["per_layer"][PARENT_ENTRIES["per_layer"]] == metric
    cells = [w["name"] for w in m["workloads"]]
    for e in m["end_to_end"] + m["per_layer"]:
        if e.get("workloads") is not None:
            kept = [w for w in e["workloads"] if w in cells]
            assert e["workloads"][:len(kept)] == kept
            e["workloads"] = kept
    text = json.dumps(m, indent=1) + "\n"
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_MANIFEST


@pytest.mark.parametrize("cell, seed, want", [
    # every retired batch of the traced wave committed by the batch tail
    ("basic-5k-50k.waves", "3000000019", 100.0),
    ("prefaffinity-5k.waves", "3000000019", 100.0),
    # a toy restore fits the program's event journal, so the hint survives
    # and every wave is hint-bound: no batch retires, nothing to read (at
    # the published size the cell retires ten a wave and reads 100)
    ("basic-5k.waves", "7", None),
])
def test_rehearsal_of_an_unlisted_wave_cell_under_a_manifest_that_lists_it(
        cell, seed, want, tmp_path):
    m = json.loads(json.dumps(MANIFEST))
    (metric,) = [e for e in m["per_layer"] if e["name"] == METRIC]
    if cell not in metric["workloads"]:
        metric["workloads"].append(cell)
    copy = tmp_path / "BENCHMARK.json"
    copy.write_text(json.dumps(m, indent=1) + "\n")
    env = {k: v for k, v in os.environ.items()
           if k not in ("BENCH_RUN", "XLA_FLAGS")}
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", seed, "--seconds", "1", "--trace", "1", "--rehearse",
         "--manifest", str(copy)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    waves = [l for l in proc.stdout.splitlines() if "] wave " in l]
    assert waves
    if want is None:
        assert all(" batches 0 " in l for l in waves)
        assert METRIC not in line["metrics"]
    else:
        assert line["metrics"][METRIC]["value"] == want
