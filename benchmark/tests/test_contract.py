"""BENCHMARK.json against the contract's static rules, and the files each
entry names."""

import importlib.util
import json
import os
import re

from benchmark.lib import model

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj\w*)_size|_dim$|"
                   r"_rank$|expansion|experts_per_tok")


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_top_level_keys_and_limits():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536
    assert 1 <= len(b["workloads"]) <= 24 and 1 <= len(b["configs"]) <= 24
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)
    # a full check fits: 2 + 14 runs a cell at the full 24 cells
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_whys():
    b = bench()
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in b[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])


def test_configs_name_their_files_and_cut_no_width():
    b = bench()
    used = {w["config"] for w in b["workloads"]}
    files = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(REPO, c["file"])) as fh:
            conf = json.load(fh)
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
            assert key in conf["changed"]
        # the source's config.json as published (configs/published/), but
        # for what `reduced` lists: no family's numbers stand in this test
        published = model.load_published(conf)
        assert published["source"] == c["source"]
        shared = (set(published) & set(conf)) - model.BOOKKEEPING_KEYS
        assert shared >= {"model_type", "hidden_size", "num_hidden_layers",
                          "vocab_size"}, c["name"]
        for key in shared:
            if key in c["reduced"]:
                assert conf["changed"][key] == {"published": published[key],
                                                "run": conf[key]}, key
            else:
                assert conf[key] == published[key], (c["name"], key)
        assert set(conf["changed"]) == set(c["reduced"])


def test_every_cell_and_metric_finds_its_files():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for w in b["workloads"]:
        for sub, name in (("configs", w["config"]),
                          ("traffic", w["traffic"])):
            assert os.path.isfile(os.path.join(
                REPO, "benchmark", sub, name + ".json")), (sub, name)
        mine = [m for m in b["end_to_end"]
                if "workloads" not in m or w["name"] in m["workloads"]]
        assert {"setup_s"} < {m["name"] for m in mine}
        assert any("workloads" not in m or w["name"] in m["workloads"]
                   for m in b["per_layer"])
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert set(m.get("workloads", cells)) <= cells
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= \
            set(moved.get("workloads", cells)), m["name"]
        path = os.path.join(REPO, "benchmark", "layer_metrics",
                            m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("reader", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.LAYER == m["layer"] and mod.MOVES == m["moves"]
        assert callable(mod.read)


def test_no_cell_name_in_harness_code_and_no_forbidden_import():
    b = bench()
    cells = [w["name"] for w in b["workloads"]]
    root = os.path.join(REPO, "benchmark")
    for dirpath, _dirs, files in os.walk(root):
        if os.sep + "tests" in dirpath or "__pycache__" in dirpath:
            continue
        for f in files:
            if not f.endswith(".py"):
                continue
            with open(os.path.join(dirpath, f)) as fh:
                text = fh.read()
            for cell in cells:
                assert cell not in text, (f, cell)
            assert not re.search(
                r"^\s*(import|from)\s+(bench|bench_inference|chip_smoke)\b",
                text, re.M), f
