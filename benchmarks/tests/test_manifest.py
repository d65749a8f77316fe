"""BENCHMARK.json holds to the contract, every cell's files resolve by name,
and a cell, a configuration and a metric can be ADDED as new files only."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import manifest as mf

KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return mf.load_manifest()


def test_top_level_and_limits(manifest):
    assert set(manifest) == KEYS
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(mf.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(manifest["paths"]) <= 16
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_keys(manifest):
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(manifest["paths"][0] + "/")
        assert all(mf.NAME_RE.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200 and "\n" not in w["why"]
        assert mf.NAME_RE.match(w["traffic"])
        names.append(w["name"])
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert len(m["layer"]) <= 200
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert mf.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert all(mf.NAME_RE.match(n) for n in names)
    assert len(names) == len(set(names))


def test_every_cell_resolves_and_reports(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    used = set()
    for w in manifest["workloads"]:
        r = mf.resolve_cell(manifest, w["name"])
        assert os.path.isfile(r["config_file"]) and os.path.isfile(r["traffic_file"])
        with open(r["config_file"]) as f:
            cfg = json.load(f)
        with open(r["traffic_file"]) as f:
            traffic = json.load(f)
        mf.load_plugin("runners", cfg["kind"])
        mf.load_plugin("generators", traffic["generator"])
        used.add(w["config"])
        e2e = mf.metrics_for(manifest, w["name"], "end_to_end")
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        per = mf.metrics_for(manifest, w["name"], "per_layer")
        assert per
        reported = {m["name"] for m in e2e}
        for m in e2e + per:
            spec = mf.metric_file(m["name"])
            assert spec["unit"] == m["unit"]
            assert hasattr(mf.load_plugin("readers", spec["reader"]), "read")
            if "moves" in m:
                assert m["moves"] in reported and spec["moves"] == m["moves"]
                assert spec["layer"] == m["layer"]
    assert used == {c["name"] for c in manifest["configs"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", [])) <= cells


def test_configs_keep_the_published_widths(manifest):
    published = {"hidden_size": 4096, "intermediate_size": 14336,
                 "num_attention_heads": 32, "num_key_value_heads": 8,
                 "head_dim": 128, "vocab_size": 32768, "rope_theta": 1000000.0,
                 "rms_norm_eps": 1e-05, "num_hidden_layers": 32,
                 "max_position_embeddings": 32768, "sliding_window": None,
                 "tie_word_embeddings": False, "torch_dtype": "bfloat16"}
    for c in manifest["configs"]:
        with open(os.path.join(mf.ROOT, c["file"])) as f:
            cfg = json.load(f)
        changed = {k for k, v in published.items() if cfg[k] != v}
        assert changed == set(c["reduced"]) == {"num_hidden_layers"}
        assert set(cfg["reduced"]) == set(c["reduced"]) and cfg["assumed"]
        assert cfg["hbm_reckoning"]
    serve = json.load(open(os.path.join(
        mf.ROOT, "benchmarks/configs/mistral-7b-v0.3-serve.json")))
    assert serve["deployment"]["decode_chunk"] == 8


def test_a_cell_a_configuration_and_a_metric_drop_in_as_new_files(tmp_path):
    """Copy the benchmark, ADD four files and three manifest entries, edit no
    file that was there, and the harness picks all of it up."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(mf.ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = mf.load_manifest()
    b = root / "benchmarks"
    (b / "configs" / "dummy-model.json").write_text(json.dumps({
        "kind": "dummy_runner", "vocab_size": 16, "deployment": {}}))
    (b / "traffic" / "dummy_mix.json").write_text(json.dumps({
        "generator": "token_dataset", "params": {"seq": 4, "rows": 2}}))
    (b / "metrics" / "dummy_metric.json").write_text(json.dumps({
        "name": "dummy_metric", "layer": "Dummy", "unit": "things",
        "moves": "setup_s", "reader": "dummy_reader", "params": {"scale": 2}}))
    (b / "readers" / "dummy_reader.py").write_text(
        "def read(ctx, params):\n    return ctx['things'] * params['scale']\n")
    (b / "runners" / "dummy_runner.py").write_text(
        "def run(*a):\n    return {'things': 21, 'setup_s': 1.0}\n")
    manifest["configs"].append({"name": "dummy-model", "source": "none",
                                "file": "benchmarks/configs/dummy-model.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "dummy_cell", "config": "dummy-model",
                                  "traffic": "dummy_mix", "chips": 4, "why": "test"})
    manifest["per_layer"].append({
        "name": "dummy_metric", "unit": "things", "better": "higher",
        "source": "program_counter", "layer": "Dummy", "moves": "setup_s",
        "workloads": ["dummy_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    code = (
        "import sys, json; sys.path.insert(0, %r)\n"
        "from benchmarks.harness import manifest as mf\n"
        "m = mf.load_manifest()\n"
        "r = mf.resolve_cell(m, 'dummy_cell')\n"
        "cfg = json.load(open(r['config_file']))\n"
        "ctx = mf.load_plugin('runners', cfg['kind']).run()\n"
        "print(json.dumps({'root': mf.ROOT, 'chips': r['cell']['chips'],\n"
        "  'per': mf.read_metrics(m, 'dummy_cell', 'per_layer', ctx),\n"
        "  'e2e': mf.read_metrics(m, 'dummy_cell', 'end_to_end', ctx)}))\n" % str(root))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=str(root), check=True).stdout
    got = json.loads(out.strip().splitlines()[-1])
    assert got["root"] == str(root) and got["chips"] == 4
    assert got["per"] == {"dummy_metric": {"value": 42, "unit": "things"}}
    assert got["e2e"] == {"setup_s": {"value": 1.0, "unit": "s"}}


def test_no_result_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` the command exits non-zero and prints no result."""
    root = tmp_path / "bare"
    shutil.copytree(os.path.join(mf.ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "train_4k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=str(root), env=env)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
