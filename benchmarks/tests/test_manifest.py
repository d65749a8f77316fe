"""BENCHMARK.json holds to the contract, every cell's files resolve by name,
every configuration keeps its source's published shape but for what it lists
as reduced, and a cell, a configuration of another family and a metric can be
ADDED as new files only: the copy that has them passes this whole contract,
and so does every family's test that reads the manifest or ``metrics/``."""
import ast
import json
import os
import re
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


def test_every_moves_names_an_end_to_end_metric_of_the_same_cells(manifest):
    """A per-layer metric, in ``BENCHMARK.json`` and in its own file, moves an
    end-to-end metric that EVERY cell it lists reports (a cell that judges
    another one reads the quantity under another name: the ``.chat`` twins);
    and every file under ``metrics/`` is a metric of the manifest whose reader
    is there."""
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    per = {m["name"]: m for m in manifest["per_layer"]}
    for m in per.values():
        reported_in = e2e[m["moves"]].get("workloads", cells)
        for cell in m.get("workloads", []):
            assert cell in reported_in, (m["name"], m["moves"], cell)
    base = os.path.join(mf.ROOT, manifest["paths"][0])
    for fname in sorted(os.listdir(os.path.join(base, "metrics"))):
        with open(os.path.join(base, "metrics", fname)) as f:
            spec = json.load(f)
        assert fname == spec["name"] + ".json"
        assert spec["name"] in e2e or spec["name"] in per, fname
        assert os.path.isfile(os.path.join(base, "readers", spec["reader"] + ".py")), \
            (fname, spec["reader"])
        if spec["name"] in per:
            assert spec["moves"] == per[spec["name"]]["moves"], fname
        else:
            assert "moves" not in spec, fname


def test_one_entry_a_meaning(manifest):
    """``per_layer`` was full at 128 of 128 (PR 48) because a family brought
    its own copy of a quantity another family already read. Every entry lists
    at least one cell that exists, and no two entries share reader, parameters
    and ``moves``: a twin is one entry with both cells in its ``workloads``;
    what a family decides (a program's name, the experts held) is named in the
    parameters and stated in the family's or the configuration's own file
    (``manifest.resolve_params``)."""
    cells = {w["name"] for w in manifest["workloads"]}
    seen = {}
    for m in manifest["per_layer"]:
        assert m.get("workloads"), m["name"]
        assert set(m["workloads"]) & cells, m["name"]
        assert len(set(m["workloads"])) == len(m["workloads"]), m["name"]
        spec = mf.metric_file(m["name"])
        key = (spec["reader"], json.dumps(spec.get("params", {}), sort_keys=True),
               m["moves"])
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]


def test_configs_keep_the_published_widths(manifest):
    """EVERY configuration names the file that holds its source's published
    shape (``benchmarks/published/<name>.json``), and differs from it in
    exactly the keys ``reduced`` lists, here and in ``BENCHMARK.json``. Which
    keys those are is the configuration's business (a depth, a count of what
    this chip holds of a stated deployment); that each has its reason is not."""
    base = os.path.join(mf.ROOT, manifest["paths"][0])
    cut = {}
    for c in manifest["configs"]:
        with open(os.path.join(mf.ROOT, c["file"])) as f:
            cfg = json.load(f)
        with open(os.path.join(base, "published", cfg["published"] + ".json")) as f:
            published = json.load(f)
        assert published["source"] == cfg["source"] == c["source"]
        missing = object()
        changed = {k for k, v in published["config"].items()
                   if cfg.get(k, missing) != v}
        assert changed == set(c["reduced"]) == set(cfg["reduced"]), (c["name"], changed)
        assert all(isinstance(why, str) and why.strip()
                   for why in cfg["reduced"].values())
        assert cfg["assumed"] and cfg["hbm_reckoning"]
        assert cfg["deployment"]["stands_for"]
        mf.family_of(cfg)
        cut[c["name"]] = changed
    assert cut["mistral-7b-v0.3-serve"] == cut["mistral-7b-v0.3-train"] \
        == {"num_hidden_layers"}
    serve = json.load(open(os.path.join(
        mf.ROOT, "benchmarks/configs/mistral-7b-v0.3-serve.json")))
    assert serve["deployment"]["decode_chunk"] == 8


# A configuration of another shape than the benchmark's own: no Mistral key
# beyond ``vocab_size``; two keys cut, one of them a count of what is held here.
DUMMY_PUBLISHED = {"vocab_size": 64, "model_width": 8, "num_blocks": 12,
                   "num_tables": 16, "mixer": {"taps": 4, "gate": "relu2"}}
DUMMY_CONFIG = {
    "kind": "dummy_runner", "family": "dummy_family", "source": "none",
    "published": "dummy-model", "vocab_size": 64, "model_width": 8,
    "num_blocks": 3, "num_tables": 8, "mixer": {"taps": 4, "gate": "relu2"},
    "reduced": {"num_blocks": "12 -> 3: a test",
                "num_tables": "16 -> 8: the half this chip holds of two"},
    "assumed": {"weights": "seeded"}, "hbm_reckoning": {"sum": "nothing"},
    "deployment": {"stands_for": "a test"}, "rehearsal": {}}
DUMMY_FAMILY = '''
"""A family of another shape: one table, looked up and multiplied back."""
import jax
import jax.numpy as jnp

from benchmarks.harness.reference import gap_fn_of, mm
from benchmarks.harness.weights import seed_key


def program_config(cfg):
    return {"vocab": cfg["vocab_size"], "width": cfg["model_width"],
            "tables": cfg["num_tables"]}


def init_weights(config, key):
    return {"tables": jax.random.normal(
        key, (config["tables"], config["vocab"], config["width"]), jnp.float32)}


def make_weights(config, seed):
    return jax.jit(lambda k: init_weights(config, k))(seed_key(seed))


def reference_logits(params, tokens, cfg, quant=None):
    table = jnp.sum(params["tables"], axis=0)
    return mm(table[tokens], table.T, quant)


def make_gap_fn(cfg, quant=None):
    return gap_fn_of(lambda p, t: reference_logits(p, t, cfg, quant))
'''
# what its runner hands the readers: its own counter, and what the two
# metrics that take the cell into their ``workloads`` read
DUMMY_CTX = {"things": 21, "setup_s": 1.0, "chips": 4,
             "cfg": {"deployment": {"warmup_steps": 1}},
             "input_waits": [9.0, 0.002, 0.004],
             "reports": [10.0, 20.0, 30.0], "report_tokens": [5.0, 400.0, 400.0],
             "window_open": 10.0, "window_close": 35.0}
# what a family's test reads the manifest or the ``metrics/`` directory through
READS_THE_MANIFEST = re.compile(
    r"load_manifest|metrics_for|metric_file|read_metrics?\b|resolve_cell"
    r"|resolve_params|BENCHMARK\.json|metrics/|[\"']metrics[\"']")


def family_tests_that_read_the_manifest(tests_dir):
    """Node ids of the tests of every ``test_*_family.py`` under ``tests_dir``
    whose text reads the manifest or ``metrics/``, or names a helper or a
    fixture of its module that does. The others (a program against its
    reference at tiny widths, a control) read neither and cost a minute."""
    ids = []
    for path in sorted(tests_dir.glob("test_*_family.py")):
        src = path.read_text()
        text = {n.name: ast.get_source_segment(src, n)
                for n in ast.parse(src).body if isinstance(n, ast.FunctionDef)}
        reads = {n for n, t in text.items() if READS_THE_MANIFEST.search(t)}
        while True:
            more = {n for n, t in text.items() if n not in reads
                    and any(re.search(r"\b%s\b" % r, t) for r in reads)}
            if not more:
                break
            reads |= more
        ids += [f"{path}::{n}" for n in text
                if n in reads and n.startswith("test_")]
    return ids


DROP_IN = '''
import json, sys
sys.path.insert(0, %r); sys.path.append(%r)
import numpy as np
import pytest
from benchmarks.harness import manifest as mf
from benchmarks.harness.weights import load_config_file
from benchmarks.tests import test_manifest as contract
m = mf.load_manifest()
r = mf.resolve_cell(m, "dummy_cell")
cfg = json.load(open(r["config_file"]))
ctx = mf.load_plugin("runners", cfg["kind"]).run()
# the whole contract of the manifest, against THIS copy
contract.test_top_level_and_limits(m)
contract.test_names_units_and_keys(m)
contract.test_every_cell_resolves_and_reports(m)
contract.test_every_moves_names_an_end_to_end_metric_of_the_same_cells(m)
contract.test_one_entry_a_meaning(m)
contract.test_configs_keep_the_published_widths(m)
# every family of the copy: a configuration, weights at tiny widths, one
# call of its reference
shapes = {}
tokens = np.arange(8, dtype=np.int32)
for c in m["configs"]:
    cfg = load_config_file(mf.ROOT + "/" + c["file"], rehearse=True)
    family = mf.family_of(cfg)
    params = family.make_weights(family.program_config(cfg), 3000000019)
    logits = family.reference_logits(params, tokens, cfg)
    gaps = family.make_gap_fn(cfg)(params, tokens, np.argmax(logits, -1))
    assert float(abs(gaps).max()) == 0.0
    shapes[c["name"]] = [family.__name__, list(logits.shape)]
# every family's tests of the manifest and of metrics/, against THIS copy: one
# that pins a count or a set fails in the PR that writes it, not in the next
assert pytest.main(["-q", "-p", "no:cacheprovider", "-p", "no:xdist"] + %r) == 0
print(json.dumps({"root": mf.ROOT, "chips": r["cell"]["chips"], "shapes": shapes,
  "per": mf.read_metrics(m, "dummy_cell", "per_layer", ctx),
  "e2e": mf.read_metrics(m, "dummy_cell", "end_to_end", ctx)}))
'''


@pytest.mark.parametrize("unlisted", [None, "model_width"],
                         ids=["whole_contract", "unlisted_change_fails"])
def test_a_cell_a_configuration_and_a_metric_drop_in_as_new_files(tmp_path, unlisted):
    """Copy the benchmark, ADD a configuration of another family (its file,
    its published file, its family module), a traffic file, a metric with its
    reader, a runner, and manifest entries (the cell appended to the
    ``workloads`` of the metrics it reports); edit no file that was there. The
    copy then passes the whole contract above, its families answer, and the
    harness reads the new metric. Two more made-up entries list an ACCEPTED
    train cell and an ACCEPTED serving cell, as a later PR's new entry would:
    every family's tests that read the manifest or ``metrics/`` pass against
    the copy too (PR 56: a count of ``per_layer`` pinned in one of them had
    shut every later entry out). A value changed without an entry
    in ``reduced`` fails the same contract."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(mf.ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = mf.load_manifest()
    b = root / "benchmarks"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    config = dict(DUMMY_CONFIG)
    if unlisted:
        config[unlisted] = 4
    (b / "configs" / "dummy-model.json").write_text(json.dumps(config))
    (b / "published" / "dummy-model.json").write_text(json.dumps(
        {"source": "none", "config": DUMMY_PUBLISHED}))
    (b / "families" / "dummy_family.py").write_text(DUMMY_FAMILY)
    (b / "traffic" / "dummy_mix.json").write_text(json.dumps({
        "generator": "token_dataset", "params": {"seq": 4, "rows": 2}}))
    (b / "metrics" / "dummy_metric.json").write_text(json.dumps({
        "name": "dummy_metric", "layer": "Dummy", "unit": "things",
        "moves": "train_tokens_per_s_chip", "reader": "dummy_reader",
        "params": {"scale": 2}}))
    (b / "readers" / "dummy_reader.py").write_text(
        "def read(ctx, params):\n    return ctx['things'] * params['scale']\n")
    (b / "runners" / "dummy_runner.py").write_text(
        "def run(*a):\n    return %r\n" % DUMMY_CTX)
    manifest["configs"].append({"name": "dummy-model", "source": "none",
                                "file": "benchmarks/configs/dummy-model.json",
                                "reduced": ["num_blocks", "num_tables"],
                                "why": "test"})
    manifest["workloads"].append({"name": "dummy_cell", "config": "dummy-model",
                                  "traffic": "dummy_mix", "chips": 4, "why": "test"})
    # an end-to-end metric that is there, and a per-layer metric that moves
    # it, take the new cell as one more entry of their ``workloads``
    for group, name in (("end_to_end", "train_tokens_per_s_chip"),
                        ("per_layer", "input_wait_per_step")):
        next(m for m in manifest[group] if m["name"] == name)[
            "workloads"].append("dummy_cell")
    manifest["per_layer"].append({
        "name": "dummy_metric", "unit": "things", "better": "higher",
        "source": "program_counter", "layer": "Dummy",
        "moves": "train_tokens_per_s_chip", "workloads": ["dummy_cell"]})
    # what a later PR's entry looks like: a cell that is there, at the END
    for name, cell, moves, scale in (
            ("dummy_train_entry", "train_moe_8k", "train_tokens_per_s_chip", 3),
            ("dummy_serve_entry", "serve_chat", "tpot_p50", 5)):
        (b / "metrics" / (name + ".json")).write_text(json.dumps({
            "name": name, "layer": "Dummy", "unit": "things", "moves": moves,
            "reader": "dummy_reader", "params": {"scale": scale}}))
        manifest["per_layer"].append({
            "name": name, "unit": "things", "better": "higher",
            "source": "program_counter", "layer": "Dummy", "moves": moves,
            "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert all(p.read_bytes() == data for p, data in before.items())
    family_tests = family_tests_that_read_the_manifest(b / "tests")
    assert family_tests
    p = subprocess.run([sys.executable, "-c",
                        DROP_IN % (str(root), mf.ROOT, family_tests)],
                       capture_output=True, text=True, cwd=str(root),
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    if unlisted:
        assert p.returncode != 0 and "AssertionError" in p.stderr
        assert "'dummy-model', {" in p.stderr and unlisted in p.stderr
        return
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["root"] == str(root) and got["chips"] == 4
    assert got["per"] == {
        "dummy_metric": {"value": 42, "unit": "things"},
        "input_wait_per_step": {"value": pytest.approx(3.0), "unit": "ms"}}
    assert got["e2e"] == {
        "setup_s": {"value": 1.0, "unit": "s"},
        "train_tokens_per_s_chip": {"value": 10.0, "unit": "tokens/s/chip"}}
    assert got["shapes"]["dummy-model"] == ["benchmarks.families.dummy_family", [8, 64]]
    assert got["shapes"]["mistral-7b-v0.3-serve"] == [
        "benchmarks.families.llama", [8, 256]]


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
