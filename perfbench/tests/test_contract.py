"""``BENCHMARK.json`` against the static limits of the contract it is
written to, and every name in it against the data file the harness finds
by that name. A later PR that adds a cell runs this before it spends chip
time: a file outside the limits is refused before a single run."""

import json
import os
import re

import pytest

from perfbench.harness import spec, traffic

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_size|expansion|experts_per_tok")


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(bench["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in bench["paths"])
    assert 1 <= len(bench["command"]) <= 32 and all(map(line, bench["command"]))
    assert isinstance(bench["run_seconds"], int) \
        and 1 <= bench["run_seconds"] <= 51
    # a full check with all 24 cells has to fit
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    names = [c["name"] for c in bench["configs"]]
    files = [c["file"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert len(c["reduced"]) <= 16
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTH.search(k), k
            assert k in cfg["published"], f"{k}: the published value is kept"
        spec.reference_module(cfg)              # its plain reference exists


def test_workloads(bench):
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in bench["configs"]}
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        mix = spec.load_traffic(w["traffic"])
        assert mix["kind"] in traffic.KINDS and line(mix["users"])
        if mix["kind"] != "train":      # lengths and arrivals name a source
            assert mix["source"] and all("from" in mix[k]
                                         for k in ("prompt", "output"))


def test_metrics(bench):
    e2e, per = bench["end_to_end"], bench["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(per) <= 128
    names = [m["name"] for m in e2e + per]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in bench["workloads"]}
    by_name = {m["name"]: m for m in e2e}
    assert "setup_s" in by_name and "workloads" not in by_name["setup_s"]
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in per:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])
        moved = by_name[m["moves"]]
        reports = set(moved.get("workloads", cells))
        assert set(m.get("workloads", reports)) <= reports
        rd = spec.load_metric_reader(m["name"])         # its reader's file
        assert callable(spec.resolve_reader(rd))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"
    for m in e2e + per:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for w in bench["workloads"]:
        mine = [m["name"] for m in spec.cell_metrics(bench, w, "end_to_end")]
        assert "setup_s" in mine and len(mine) >= 2, w["name"]
        assert spec.cell_metrics(bench, w, "per_layer"), w["name"]
    # a kernel's roofline stands beside the whole step's share of the peak
    for m in per:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in per), m["name"]


@pytest.mark.parametrize("sub", ["configs", "traffic", "metrics", "harness",
                                 "reference", "tests", "tools"])
def test_file_names_are_made_of_a_names_characters(sub):
    top = os.path.join(ROOT, "perfbench", sub)
    for d, _, files in os.walk(top):
        if "__pycache__" in d:
            continue
        for f in files:
            assert PATH.match(os.path.relpath(os.path.join(d, f), ROOT)), f
