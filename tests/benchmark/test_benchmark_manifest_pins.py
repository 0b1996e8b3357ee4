"""The one rule that every test of `BENCHMARK.json` under tests/benchmark/
keeps (benchmark/README.md, "Adding things"): an accepted entry is unchanged
but for cells appended to its `workloads`; entries and cells are appended; a
test pins the entries and cells its own PR added, by name, and never that
nothing came after them.

Proved by doing what the next PR will do: on a copy of the manifest, one cell
appended, one per-layer entry appended with its reader, the cell listed under
the new entry and under `collector_pause_share`; then every manifest assertion
of tests/benchmark/ is run against the copy. Those are found by a convention,
so that a later PR's own pins are held to the rule without an edit here: a
test function that takes no argument, names the module's `MANIFEST` (read from
`BENCHMARK.json` as the module loads) and starts no process."""

import ast
import glob
import importlib.util
import json
import os
import shutil

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

NEW_CELL = {"name": "basic-5k.waves-next", "config": "basic-5k",
            "traffic": "waves-1traced", "chips": 1,
            "why": "what the next PR appends: a cell of a pair that is free"}
NEW_METRIC = {"name": "added_by_the_next_pr", "unit": "%", "better": "lower",
              "source": "program_span", "layer": "host scheduler loop",
              "moves": "pods_per_s", "workloads": [NEW_CELL["name"]]}
ALSO_UNDER = ("collector_pause_share", "pods_per_s")


def _manifest_assertions():
    """(file, function) of every zero-argument test under tests/benchmark/
    that names `MANIFEST` and starts no process."""
    found = []
    for path in sorted(glob.glob(os.path.join(HERE, "test_*.py"))):
        if os.path.samefile(path, __file__):
            continue
        with open(path) as f:
            src = f.read()
        for node in ast.parse(src).body:
            if not (isinstance(node, ast.FunctionDef)
                    and node.name.startswith("test_")):
                continue
            body = ast.get_source_segment(src, node)
            takes = node.args.args or node.decorator_list
            if "MANIFEST" in body and not takes and not any(
                    s in body for s in ("subprocess", "_run(", "_rehearse(")):
                found.append((os.path.basename(path), node.name))
    return found


ASSERTIONS = _manifest_assertions()


def with_the_addition(manifest: dict) -> dict:
    m = json.loads(json.dumps(manifest))
    m["workloads"].append(dict(NEW_CELL))
    m["per_layer"].append(json.loads(json.dumps(NEW_METRIC)))
    for e in m["end_to_end"] + m["per_layer"]:
        if e["name"] in ALSO_UNDER:
            e["workloads"].append(NEW_CELL["name"])
    return m


@pytest.fixture(scope="module")
def bench_with_the_reader(tmp_path_factory):
    """benchmark/'s traffic files and readers, and the new entry's reader:
    where the contract's test looks for a file by a manifest's name."""
    bench = tmp_path_factory.mktemp("bench")
    for d in ("traffic", "layer_metrics"):
        shutil.copytree(os.path.join(BENCH, d), bench / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "layer_metrics" / (NEW_METRIC["name"] + ".py")).write_text(
        "def read(obs):\n    return None\n")
    return str(bench)


def _load(filename):
    spec = importlib.util.spec_from_file_location(
        "pinned_" + filename[:-3], os.path.join(HERE, filename))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_convention_finds_the_manifests_tests():
    assert len(ASSERTIONS) >= 12
    files = {f for f, _ in ASSERTIONS}
    assert {"test_benchmark_harness.py", "test_benchmark_antiaffinity.py",
            "test_benchmark_basic50k.py", "test_benchmark_prefaffinity.py",
            "test_benchmark_commit_tail.py", "test_benchmark_pop_run.py",
            "test_benchmark_timeline.py"} <= files
    assert ("test_benchmark_harness.py",
            "test_manifest_keeps_to_the_contract") in ASSERTIONS
    assert ("test_benchmark_timeline.py",
            "test_the_manifest_lists_the_eight_where_the_issue_says"
            ) in ASSERTIONS


@pytest.mark.parametrize("filename, function", ASSERTIONS)
def test_an_appended_cell_and_metric_pass_every_manifest_assertion(
        filename, function, bench_with_the_reader):
    mod = _load(filename)
    assert mod.MANIFEST == MANIFEST
    mod.MANIFEST = with_the_addition(MANIFEST)
    if hasattr(mod, "BENCH"):
        # only the contract's test looks under it, for traffic and readers
        if function == "test_manifest_keeps_to_the_contract":
            mod.BENCH = bench_with_the_reader
    getattr(mod, function)()


def test_an_edit_to_an_accepted_entry_still_fails():
    """The rule is not "anything goes": a cell put in between, an entry
    changed or an entry put in between is refused by the pins that hold it."""
    mod = _load("test_benchmark_pop_run.py")
    hold = mod.test_the_parents_entries_are_untouched_and_the_entry_is_this_one
    for spoil in ("unit", "between", "inserted"):
        m = with_the_addition(MANIFEST)
        if spoil == "unit":
            m["per_layer"][3]["unit"] = "share"
        elif spoil == "between":
            m["per_layer"][0]["workloads"].insert(1, NEW_CELL["name"])
        else:
            m["per_layer"].insert(5, m["per_layer"].pop())
        mod.MANIFEST = m
        with pytest.raises(AssertionError):
            hold()
    timeline = _load("test_benchmark_timeline.py")
    m = with_the_addition(MANIFEST)
    by_name = {e["name"]: e for e in m["per_layer"]}
    by_name["collector_pause_share"]["workloads"].remove("basic-5k.waves")
    timeline.MANIFEST = m
    with pytest.raises(AssertionError):
        timeline.test_the_manifest_lists_the_eight_where_the_issue_says()
