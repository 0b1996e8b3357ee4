"""What PR 51 added to the benchmark: one reader and one entry of `per_layer`,
`mirror_rows_by_column_share` (of the mirror rows that the traced waves'
builds brought in line with their nodes, the share that took the column pass:
the `sched.plan.build` spans' stat `rows_by_column` over it and their stat
`rows_encoded`). Here: the entry pinned by name, the reader on made-up
observations, a traced rehearsal of a listed cell, whose restore dirties the
rows the next wave's build finds (100 %), and a rehearsal of `basic-5k.waves`
under a copy of the manifest that lists it: ISSUE 51 wanted the cell listed,
and at toy size its waves are hint-bound, open no `plan.build` and leave the
reader nothing, which the accepted harness test does not allow a listed cell
(`test_rehearsal_prints_the_contracts_line`; PERF.md section 7). No timing is
asserted."""

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
METRIC = "mirror_rows_by_column_share"
LISTED_CELLS = ["antiaffinity-5k.waves", "spread-5k.waves",
                "basic-5k-50k.waves"]
ENTRY = {"name": METRIC, "unit": "%", "better": "higher",
         "source": "program_span", "layer": "feature build and mirror",
         "moves": "pods_per_s", "workloads": LISTED_CELLS}


def _reader():
    path = os.path.join(BENCH, "layer_metrics", METRIC + ".py")
    spec = importlib.util.spec_from_file_location("under_test_" + METRIC, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_entry_is_appended_and_is_this_one():
    metrics = [m["name"] for m in MANIFEST["per_layer"]]
    # after the parent's last, wherever a later PR has put its own
    assert metrics.index(METRIC) > metrics.index("plan_node_shapes")
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == METRIC]
    assert dict(entry, workloads=None) == dict(ENTRY, workloads=None)
    assert entry["workloads"][:len(LISTED_CELLS)] == LISTED_CELLS
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", METRIC + ".py"))
    # every cell it lists reports the metric it moves, from waves
    reports = {w for e in MANIFEST["end_to_end"] if e["name"] == ENTRY["moves"]
               for w in e["workloads"]}
    assert set(entry["workloads"]) <= reports
    by_name = {w["name"]: w for w in MANIFEST["workloads"]}
    assert all(by_name[w]["traffic"].startswith("waves")
               for w in entry["workloads"])
    # the layer is one the manifest already names, letter for letter
    assert ENTRY["layer"] in {m["layer"] for m in MANIFEST["per_layer"]
                              if m["name"] != METRIC}


BENCH_SPANS = [["bench.init", 0.0, 90.0], ["bench.wave", 100.0, 50.0],
               ["bench.restore", 150.0, 20.0], ["bench.wave", 300.0, 50.0]]


@pytest.mark.parametrize("encoded, by_column, waves, want", [
    # a wave-start build that finds the restore's rows, their nodes unchanged
    ([[101.0, 0], [301.0, 0]], [[101.0, 2000], [301.0, 1990]], 2, 100.0),
    # a node changed between the waves: one row whole beside 999 by column
    ([[101.0, 0], [301.0, 1]], [[101.0, 1000], [301.0, 999]], 2, 99.95),
    # everything encoded whole (after an invalidated session): 0, not nothing
    ([[301.0, 5000]], [[301.0, 0]], 1, 0.0),
    # only the last traced wave counts; a build outside a wave does not
    ([[50.0, 5000], [101.0, 30], [301.0, 10]],
     [[50.0, 0], [101.0, 70], [301.0, 30]], 1, 75.0),
    # resumed sessions: builds that brought no row in line
    ([[101.0, 0], [301.0, 0]], [[101.0, 0], [301.0, 0]], 2, None),
    # without the stats (the parent of PR 51): nothing
    ([[101.0, None]], [[101.0, None]], 2, None),
    # no build inside a traced wave; no traced wave
    ([[50.0, 3]], [[50.0, 9]], 2, None),
    ([[101.0, 3]], [[101.0, 9]], 0, None),
])
def test_the_share_of_the_traced_builds_rows_that_went_by_column(
        encoded, by_column, waves, want):
    got = _reader().share(BENCH_SPANS, encoded, by_column, waves)
    assert got is None if want is None else got == pytest.approx(want)


def test_without_a_trace_the_reader_reads_nothing():
    assert _reader().read({"traced": {}}) is None
    assert _reader().read({}) is None


def _rehearse(cell, seed, manifest=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("BENCH_RUN", "XLA_FLAGS")}
    args = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
            "--seed", seed, "--seconds", "1", "--trace", "1", "--rehearse"]
    if manifest is not None:
        args += ["--manifest", str(manifest)]
    proc = subprocess.run(args, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def test_a_traced_rehearsal_of_a_listed_cell_reads_the_restores_rows():
    line, _out = _rehearse("antiaffinity-5k.waves", "3000000051")
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    # the nodes never change: every row a traced build brought in line is
    # one the restore (or the wave before) moved the pods of
    assert line["metrics"][METRIC] == {"value": pytest.approx(100.0),
                                       "unit": "%"}
    # and the session's end is there to read
    assert "plan_adopt_share" in line["metrics"]


def test_a_hint_bound_toy_wave_leaves_the_reader_nothing(tmp_path):
    """`basic-5k.waves` at toy size: the restore fits the journal, the score
    hint survives it, no session opens in the traced waves."""
    m = json.loads(json.dumps(MANIFEST))
    (entry,) = [e for e in m["per_layer"] if e["name"] == METRIC]
    if "basic-5k.waves" not in entry["workloads"]:
        entry["workloads"].append("basic-5k.waves")
    copy = tmp_path / "BENCHMARK.json"
    copy.write_text(json.dumps(m, indent=1) + "\n")
    line, out = _rehearse("basic-5k.waves", "3000000052", copy)
    assert line["correct"] is True, line["compared"]
    waves = [l for l in out.splitlines() if "] wave " in l]
    assert waves
    if all(" batches 0 " in l for l in waves):
        assert METRIC not in line["metrics"]
    else:  # a later program that opens a session there has rows to read
        assert 0.0 <= line["metrics"][METRIC]["value"] <= 100.0
