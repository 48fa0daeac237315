"""Every cell of BENCHMARK.json resolves its pieces by name, the file keeps
to the benchmark's contract, and a new cell takes data files alone."""
import importlib
import json
import os
import re
import shutil

import pytest

from bench import harness

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = {"hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_experts_per_tok", "channels"}


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_every_piece_by_name(name):
    spec = harness.load_cell(name)
    driver = harness.driver_of(spec)
    assert callable(driver.run) and callable(driver.calibrate)
    ref = harness.reference_of(spec.config)
    assert callable(ref.init)
    assert "limits" in spec.traffic and spec.traffic["limits"]
    for metric in spec.layer_names():
        assert callable(harness.reader_of(metric))
    names = spec.e2e_names()
    assert "setup_s" in names and len(names) >= 2
    assert spec.layer_names()


def test_contract_keys_names_and_bounds():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "layer", "moves", "workloads"}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in e2e[m["moves"]].get("workloads", CELLS)
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used and NAME.match(c["name"])
        assert c["file"].startswith("bench/")
        assert not [k for k in c["reduced"]
                    if k in WIDTHS or k.endswith(("_dim", "_rank"))]
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs) == len(set(CELLS))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 2)


def test_the_harness_holds_no_cell_of_its_own():
    words = set(CELLS) | {w["traffic"] for w in BENCH["workloads"]} | {
        c["name"] for c in BENCH["configs"]}
    for f in ("run.py", "harness.py", "trace.py", "readers.py",
              "training.py", "compare.py"):
        text = open(os.path.join(ROOT, "bench", f)).read()
        assert not [w for w in words if w in text], f


def test_a_new_cell_takes_data_files_alone(tmp_path):
    """A copy of the benchmark gains a cell by a traffic file and a
    BENCHMARK.json entry; the harness finds it with no code changed."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    bench = dict(BENCH)
    base = harness.load_cell("resnet44.lb4096-gbn")
    traffic = dict(base.traffic, batch=2048)
    (tmp_path / "bench" / "traffic" / "lb2048-gbn.json").write_text(
        json.dumps(traffic))
    bench["workloads"] = BENCH["workloads"] + [{
        "name": "resnet44.lb2048-gbn", "config": "resnet44-cifar10",
        "traffic": "lb2048-gbn", "chips": 1, "why": "half the batch"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.load_cell("resnet44.lb2048-gbn", root=str(tmp_path))
    assert spec.traffic["batch"] == 2048
    assert harness.driver_of(spec) is importlib.import_module(
        "bench.drivers.train_vision")
