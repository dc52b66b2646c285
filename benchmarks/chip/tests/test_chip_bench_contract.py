"""``BENCHMARK.json`` keeps the benchmark's format, and every cell finds
its configuration, its mix, its served path and a reader for each of its
metrics by name."""
import ast
import json
import os
import re

import pytest

from chip_bench_testlib import CHIP, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank|hidden|intermediate|latent|head|"
                   r"expansion|experts_per_tok)")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_and_paths(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = bench["command"]
    assert 1 <= len(cmd) <= 32 and all(one_line(w) for w in cmd)
    for w in cmd:
        if "/" in w:
            assert any(w.startswith(p + "/") for p in bench["paths"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert (runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith(bench["paths"][0] + "/")
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert len(c["reduced"]) <= 16
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTH.search(k)
            assert cfg[k] != cfg["published"][k]
        # every published number not listed as reduced is kept
        for k, v in cfg["published"].items():
            if k not in c["reduced"]:
                assert cfg[k] == v, k
        assert len(cfg["tables"]["rows"]) == cfg["num_user_tables"]


def test_cells(bench):
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        assert os.path.exists(os.path.join(CHIP, "traffic",
                                           w["traffic"] + ".json"))


def test_metrics_and_readers(bench):
    import run
    e2e, layer = bench["end_to_end"], bench["per_layer"]
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    assert "setup_s" in names and 1 <= len(e2e) <= 16
    assert 1 <= len(layer) <= 128
    for m in e2e:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    cells = {w["name"] for w in bench["workloads"]}
    reports = {c: {m["name"] for m in e2e
                   if c in m.get("workloads", cells)} for c in cells}
    layers = {}
    for m in layer:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert one_line(m["layer"])
        assert layers.setdefault(m["name"].split(".")[0],
                                 m["layer"]) == m["layer"]
        assert m["moves"] in {x["name"] for x in e2e}
        for c in m["workloads"]:
            assert m["moves"] in reports[c]
        assert callable(run.reader(m["name"]))
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2
        assert any(c in m["workloads"] for m in layer)
        assert all(m["name"] in {x["name"] for x in e2e}
                   for m in run.resolve(bench, c).e2e)


SERVED_API = ("build", "inputs", "shape", "serve", "counters", "attach",
              "check", "step_work")


def test_every_configuration_names_a_served_path(bench):
    import run
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            name = json.load(f).get("served", run.DEFAULT_SERVED)
        assert NAME.match(name)
        assert os.path.exists(os.path.join(CHIP, "served", name + ".py"))
        mod = run.served_module(name)
        assert all(callable(getattr(mod, fn)) for fn in SERVED_API)


def test_harness_imports_no_served_path():
    """What belongs to one kind of query lives in ``served/``: the harness
    imports neither the engine nor the reference, and touches no attribute
    of the engine it is handed."""
    with open(os.path.join(CHIP, "run.py")) as f:
        tree = ast.parse(f.read())
    imported, touched = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
        elif (isinstance(node, (ast.Attribute, ast.Subscript))
              and isinstance(node.value, ast.Name)
              and node.value.id == "engine"):
            touched.add(ast.unparse(node))
    assert not {m for m in imported
                if m == "reference" or m.startswith("repro.runtime")}
    assert not touched
