"""Everything BENCHMARK.json names resolves to a file, and a cell, a
configuration, a mix, a runner kind, a per-layer metric and a reader are
each one new file plus one new entry."""

import json
import os
import re

import pytest

from benchmark.lib import harness

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves(cell):
    res = harness.resolve_cell(BENCH, cell)
    assert hasattr(res["runner"], "run")
    assert res["config"]["llm_config"]
    assert res["traffic"]["why"]
    e2e = [m["name"] for m in harness.metrics_of_cell(BENCH, "end_to_end",
                                                      cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = harness.metrics_of_cell(BENCH, "per_layer", cell)
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("metric,cell", [
    (m["name"], c) for m in BENCH["per_layer"]
    for c in m.get("workloads", CELLS)])
def test_every_layer_metric_resolves(metric, cell):
    """A case a reading a cell: an entry folded over several cells keeps
    one case for each of them."""
    spec, reader = harness.load_layer_metric(metric)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert callable(reader.read)
    for key in ("layer", "unit", "moves"):
        assert spec[key] == entry[key], key
    # the file's kinds and the entry's cells say the same thing
    assert harness.resolve_cell(BENCH, cell)["traffic"]["kind"] \
        in spec["kinds"]
    assert reader.read({}, spec.get("args", {})) is None   # nothing to read


def test_no_kind_without_a_cell():
    """A file's `kinds` are the runner kinds of the cells its entry lists,
    no more: a kind left behind by a retired cell would say a reading exists
    where none is taken."""
    for entry in BENCH["per_layer"]:
        spec, _ = harness.load_layer_metric(entry["name"])
        listed = entry.get("workloads", CELLS)
        kinds = {harness.resolve_cell(BENCH, c)["traffic"]["kind"]
                 for c in listed}
        assert kinds == set(spec["kinds"]), entry["name"]
        # the cells in the order of `workloads`
        assert listed == [c for c in CELLS if c in listed], entry["name"]


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for g in ("end_to_end", "per_layer")
             for m in BENCH[g]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "_roofline" not in m["name"] or m["unit"] == "%"
    # the driver's contract for BENCHMARK.json (the builder's instructions:
    # "`per_layer`: 1 to 128 metrics of single layers", "`workloads`: 1 to
    # 24 cells"; a file outside either is refused before a single run). No
    # line of the harness needs them: they stand here, and in no other test,
    # so that the repo sees how much room is left. 92 entries since PR 66's
    # fold (115 before it; 81 after PR 61's, 124 before that; 71 after PR
    # 52's); a configuration costs about 30, of which about 23 come back at
    # the next fold (PERF.md section 7)
    assert 1 <= len(BENCH["per_layer"]) <= 128
    assert 1 <= len(BENCH["workloads"]) <= 24
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200
    for c in BENCH["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert os.path.getsize(os.path.join(harness.ROOT,
                                        "BENCHMARK.json")) < 64 * 1024


def _reading(entry):
    """What makes two entries ONE reading (PERF.md section 3): the file's
    `reader` and `args` as they are written, key order too, and the entry's
    `unit`, `better`, `source`, `layer`, `moves`."""
    spec, _ = harness.load_layer_metric(entry["name"])
    return (spec["reader"], json.dumps(spec.get("args", {})), entry["unit"],
            entry["better"], entry["source"], entry["layer"], entry["moves"])


@pytest.mark.parametrize("cell", CELLS + ["the whole list"])
def test_no_cell_reads_one_reading_twice(cell):
    """Metrics that differ in name alone are ONE entry that lists their
    cells. A cell listed in such an entry AND in a twin left behind would
    report one reading under two names: within a cell no two per-layer
    metrics share (reader, args). Over the whole list no two ENTRIES are
    one reading. A configuration's twins of accepted entries (a
    `model_config` PR may add entries and edit none) are named here the day
    they land, as a skip while the list has room for one more configuration
    and as a failure once it has not: the next `benchmark` PR folds them."""
    seen = {}
    if cell == "the whole list":
        twins = []
        for m in BENCH["per_layer"]:
            first = seen.setdefault(_reading(m), m["name"])
            if first != m["name"]:
                twins.append((m["name"], first))
        # a configuration costs about 30 entries: with twins on the list and
        # less room than that, the fold is due NOW; with room, they are said
        assert not twins or len(BENCH["per_layer"]) + 30 <= 128, twins
        if twins:
            pytest.skip(f"{len(twins)} entries repeat an accepted reading, "
                        f"the next `benchmark` PR's to fold: {twins}")
        return
    for m in harness.metrics_of_cell(BENCH, "per_layer", cell):
        spec, _ = harness.load_layer_metric(m["name"])
        key = (spec["reader"], json.dumps(spec.get("args", {}),
                                          sort_keys=True))
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]


def test_one_file_an_entry():
    files = sorted(os.listdir(os.path.join(harness.BENCH_DIR,
                                           "layer_metrics")))
    assert files == sorted(m["name"] + ".json" for m in BENCH["per_layer"])


def test_missing_pieces_name_their_path(tmp_path):
    with pytest.raises(harness.Unresolved, match="no workload"):
        harness.resolve_cell(BENCH, "no_such_cell")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "x", "config": bench["configs"][0]
                               ["name"], "traffic": "no_such_mix",
                               "chips": 1, "why": "test"})
    with pytest.raises(harness.Unresolved,
                       match=r"benchmark/traffic/no_such_mix\.json"):
        harness.resolve_cell(bench, "x")
    with pytest.raises(harness.Unresolved,
                       match=r"benchmark/layer_metrics/nope\.json"):
        harness.load_layer_metric("nope")


def test_new_pieces_are_new_files_only(tmp_path, monkeypatch):
    """A mix of a new kind with a new per-layer metric read by a new
    reader: five new files and three new entries, no edit to a file that
    is there."""
    made = []

    def put(rel, text):
        path = os.path.join(harness.BENCH_DIR, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)
        made.append(path)

    try:
        put("traffic/zz_test_mix.json",
            json.dumps({"kind": "zz_test_kind", "why": "resolution test"}))
        put("runners/zz_test_kind.py",
            "def run(ctx):\n    return {'observations': {'zz': 4.0}}\n")
        put("layer_metrics/zz_test_metric.json",
            json.dumps({"name": "zz_test_metric", "layer": "device",
                        "unit": "ms", "moves": "setup_s",
                        "kinds": ["zz_test_kind"], "reader": "zz_test_reader",
                        "args": {"key": "zz"}}))
        put("readers/zz_test_reader.py",
            "def read(obs, args):\n    return obs.get(args['key'])\n")
        bench = json.loads(json.dumps(BENCH))
        bench["workloads"].append(
            {"name": "zz_cell", "config": bench["configs"][0]["name"],
             "traffic": "zz_test_mix", "chips": 1, "why": "test"})
        bench["per_layer"].append(
            {"name": "zz_test_metric", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "device", "moves": "setup_s",
             "workloads": ["zz_cell"]})
        res = harness.resolve_cell(bench, "zz_cell")
        out = res["runner"].run({})
        got = harness.read_layer_metrics(bench, "zz_cell",
                                         out["observations"])
        assert got == {"zz_test_metric": {"value": 4.0, "unit": "ms"}}
    finally:
        for path in made:
            os.remove(path)
