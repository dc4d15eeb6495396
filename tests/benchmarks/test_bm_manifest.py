"""BENCHMARK.json against the files it names, and the command itself."""
import glob
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return _load(ROOT, "BENCHMARK.json")


def test_every_name_resolves_to_one_file(manifest):
    assert manifest["command"] == ["python3", "benchmarks/run.py"]
    assert os.path.isfile(os.path.join(ROOT, manifest["command"][1]))
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    for c in manifest["configs"]:
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        cfg = _load(ROOT, c["file"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for kind in ("models", "reference"):
            assert os.path.isfile(os.path.join(BENCH, kind, cfg["family"] + ".py"))
    configs = {c["name"] for c in manifest["configs"]}
    assert {w["config"] for w in manifest["workloads"]} == configs
    for w in manifest["workloads"]:
        t = _load(BENCH, "traffic", w["traffic"] + ".json")
        assert os.path.isfile(os.path.join(BENCH, "drivers", t["driver"] + ".py"))
        assert w["chips"] == _load(ROOT, {c["name"]: c for c in manifest["configs"]}
                                   [w["config"]]["file"])["deployment"]["chips"]
    for m in manifest["per_layer"]:
        spec = _load(BENCH, "layer_metrics", m["name"] + ".json")
        assert {k: spec[k] for k in m} == m
        assert os.path.isfile(os.path.join(BENCH, "reducers",
                                           spec["reducer"] + ".py"))
    # and no stray metric file that the table does not name
    on_disk = {os.path.basename(p)[:-5]
               for p in glob.glob(os.path.join(BENCH, "layer_metrics", "*.json"))}
    assert on_disk == {m["name"] for m in manifest["per_layer"]}


def test_names_units_and_limits(manifest):
    names = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[key]:
            assert NAME.match(e["name"]), e["name"]
            names.append((key if key in ("configs", "workloads") else "metric",
                          e["name"]))
    assert len(set(names)) == len(names)
    for w in manifest["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for path in manifest["paths"]:
        for dirpath, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in dirpath:
                continue
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), (dirpath, f)


def test_moves_is_an_end_to_end_metric_of_every_cell_that_reports_it(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in m.get("workloads", e2e[m["moves"]]):
            assert cell in cells and cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:
        assert sum(cell in v for v in e2e.values()) >= 2       # setup_s + one
        assert any(cell in m.get("workloads", cells) for m in manifest["per_layer"])


def test_configurations_pin_no_choice_of_the_program(manifest):
    banned = ("ragged_tokens", "block_q", "block_pages", "lean", "tokbuf",
              "pipeline", "PT_")
    for c in manifest["configs"]:
        text = open(os.path.join(ROOT, c["file"])).read()
        dep = json.loads(text)["deployment"]
        for b in banned:
            assert b not in dep and f'"{b}' not in text, (c["name"], b)
    for src in glob.glob(os.path.join(BENCH, "**", "*.py"), recursive=True):
        assert "os.environ[\"PT_" not in open(src).read(), src


def test_published_widths_are_not_cut(manifest):
    for c in manifest["configs"]:
        cfg = _load(ROOT, c["file"])
        for k, v in cfg["model"].items():
            if k in cfg["published"] and k not in cfg["reduced"]:
                assert cfg["published"][k] == v, (c["name"], k)
        assert cfg["model"]["hidden_size"] // cfg["model"]["num_attention_heads"] \
            == cfg["published"]["head_dim"]


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "chat_saturated", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "not 'tpu'" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
