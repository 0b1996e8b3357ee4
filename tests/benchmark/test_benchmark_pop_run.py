"""What PR 37 added to the benchmark: one reader, `pop_run_share` (of the pods
that the traced waves' `sched.queue.pop` spans took into a device batch, the
share taken as a run, on the session template's verdict: the spans' stat `run`
over their stat `pods`), and NO entry of `per_layer` yet: the accepted
`test_benchmark_timeline.py` holds the last eight entries of `per_layer` to
PR 36's eight, so any entry appended after them fails it, and that file is a
`benchmark` PR's to edit. Until then the reader is run by hand, under a copy
of the manifest with the entry appended (`--manifest <copy>`). Here: the
reader on canned observations, the parent's entries of the manifest held to
what they were (entries appended after them pass, this metric's own
included), the entry as the `benchmark` PR should append it, and traced
rehearsals under a manifest that lists a claimed cell and a cell ISSUE 37
wanted listed."""

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
METRIC = "pop_run_share"
LISTED_CELLS = ["spread-5k.waves", "antiaffinity-5k.waves"]
PINNED_CELLS = ["basic-5k.waves", "prefaffinity-5k.waves",
                "basic-5k-50k.waves"]
# BENCHMARK.json at the parent commit (545f458): its sha256 and how many
# entries each list had
PARENT_MANIFEST = "3017ed44e0f91450fca0032cec51a6fa252e4ca9066c0fd9e412e6e9116a6606"
PARENT_ENTRIES = {"configs": 5, "workloads": 8, "end_to_end": 4,
                  "per_layer": 37}
# ... and how many cells each entry's `workloads` named there, entry by entry
# (None: no such key). A filter on the parent's cells would not do: a later
# PR may append a cell the parent already had (PR 38 did, to nine entries)
PARENT_LISTED = {
    "end_to_end": [6, 2, 2, None],
    "per_layer": [6, 6, 6, 6, 6, 4, 2, 2, 2, 2, 2, 6, 6, 6, 5, 2, 2, 2, 2,
                  1, 1, 1, 1, 1, 1, 1, 1, 1, 2, 2, 2, 2, 1, 4, 4, 4, 1]}


def _reader():
    path = os.path.join(BENCH, "layer_metrics", METRIC + ".py")
    spec = importlib.util.spec_from_file_location("under_test_" + METRIC, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("pods, run, waves, want", [
    # a wave of clones: all but the session's head, over the last two waves
    ([[90.0, 50], [100.0, 1024], [120.0, 976], [125.0, 0], [310.0, 2000]],
     [[90.0, 0], [100.0, 1023], [120.0, 976], [125.0, 0], [310.0, 1999]],
     2, 100.0 * 3998 / 4000),
    # the last wave alone; pods decoded from the wire are never a run
    ([[100.0, 1024], [310.0, 500], [320.0, 500]],
     [[100.0, 1023], [310.0, 499], [320.0, 0]], 1, 49.9),
    ([[100.0, 2], [310.0, 2]], [[100.0, 0], [310.0, 0]], 2, 0.0),
    # without the stats (the parent of PR 37): nothing
    ([[100.0, None], [310.0, None]], [[100.0, None], [310.0, None]], 2, None),
    # pops that took no pod (a hint-bound wave), no pop inside a traced
    # wave, no traced wave
    ([[100.0, 0], [310.0, 0]], [[100.0, 0], [310.0, 0]], 2, None),
    ([[50.0, 16], [160.0, 16]], [[50.0, 15], [160.0, 16]], 2, None),
    ([[100.0, 16]], [[100.0, 15]], 0, None),
])
def test_the_share_of_the_traced_waves_pods_popped_as_a_run(
        pods, run, waves, want):
    reader = _reader()
    bench = [["bench.init", 0.0, 90.0], ["bench.wave", 100.0, 50.0],
             ["bench.restore", 150.0, 20.0], ["bench.wave", 300.0, 50.0]]
    got = reader.share(bench, pods, run, waves)
    assert got is None if want is None else got == pytest.approx(want)
    # an untraced run, a traced run with no wave traced
    assert reader.read({}) is None
    assert reader.read({"traced": {"waves": 0}}) is None


ENTRY = {"name": METRIC, "unit": "%", "better": "higher",
         "source": "program_span", "layer": "host scheduler loop",
         "moves": "pods_per_s", "workloads": LISTED_CELLS}


def _copy_that_lists(cell, tmp_path):
    m = json.loads(json.dumps(MANIFEST))
    found = [e for e in m["per_layer"] if e["name"] == METRIC]
    if not found:
        found = [dict(ENTRY, workloads=list(LISTED_CELLS))]
        m["per_layer"] += found
    if cell not in found[0]["workloads"]:
        found[0]["workloads"].append(cell)
    copy = tmp_path / "BENCHMARK.json"
    copy.write_text(json.dumps(m, indent=1) + "\n")
    return copy


def test_the_parents_entries_are_untouched_and_the_entry_is_this_one():
    # the parent's entries are a prefix of every list, and of every entry's
    # `workloads`: cut back to them, the file is the parent's, byte for
    # byte. Whatever a later PR appends, this metric's entry, a cell, another
    # metric, a cell's name at the end of a `workloads` list, passes
    m = json.loads(json.dumps(MANIFEST))
    for key, n in PARENT_ENTRIES.items():
        assert len(m[key]) >= n
        m[key] = m[key][:n]
    for key, listed in PARENT_LISTED.items():
        for e, n in zip(m[key], listed, strict=True):
            if n is not None:
                e["workloads"] = e["workloads"][:n]
    text = json.dumps(m, indent=1) + "\n"
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_MANIFEST
    # the reader is the one file this PR put under benchmark/
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", METRIC + ".py"))
    # the entry: the layer and the end-to-end metric are ones the manifest
    # has, and every cell it may list reports that metric from waves
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] != METRIC}
    assert ENTRY["layer"] in layers
    reports = {w for e in MANIFEST["end_to_end"] if e["name"] == ENTRY["moves"]
               for w in e["workloads"]}
    assert set(LISTED_CELLS + PINNED_CELLS) <= reports
    by_name = {w["name"]: w for w in MANIFEST["workloads"]}
    assert all(by_name[w]["traffic"].startswith("waves")
               for w in LISTED_CELLS + PINNED_CELLS)
    # where the manifest has it, it is ENTRY, with more cells at most
    for metric in MANIFEST["per_layer"]:
        if metric["name"] == METRIC:
            assert dict(metric, workloads=None) == dict(ENTRY, workloads=None)
            assert metric["workloads"][:len(LISTED_CELLS)] == LISTED_CELLS
            assert set(metric["workloads"]) <= reports


@pytest.mark.parametrize("cell, seed, batches, pods", [
    # a claimed cell: a traced wave of 4,200 clones in five batches
    ("basic-5k-50k.waves", "3000000037", 5, 4200),
    # a cell ISSUE 37 wanted it listed under: 200 spread pods a wave
    ("spread-5k.waves", "3000000038", 1, 200),
])
def test_rehearsal_under_a_manifest_that_lists_the_metric(
        cell, seed, batches, pods, tmp_path):
    """One session a wave, so every pod but the session's head is popped as
    a run: (n - 1) / n of a wave's n pods."""
    copy = _copy_that_lists(cell, tmp_path)
    env = {k: v for k, v in os.environ.items()
           if k not in ("BENCH_RUN", "XLA_FLAGS")}
    for attempt in range(3):
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
             cell, "--seed", seed, "--seconds", "1", "--trace", "1",
             "--rehearse", "--manifest", str(copy)],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        waves = [l for l in proc.stdout.splitlines() if "] wave " in l]
        assert waves
        # On a loaded sandbox the thread that creates a toy wave's pods can
        # fall behind the loop: the wave splits into two sessions, and the
        # second meets a program the warm-up waves never did
        # (`compiles_in_window`; 2 of 42 rehearsals with fourteen at once,
        # none alone). That is the load's, not the program's: once more.
        over = {k for k, v in line["compared"].items()
                if v["value"] > v["limit"]}
        split = [l for l in waves if f" batches {batches} " not in l]
        if not (split and over <= {"compiles_in_window"}):
            break
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert not split, split
    assert line["metrics"][METRIC]["value"] == pytest.approx(
        100.0 * (pods - 1) / pods)
