"""Finding a cell's files by the names in ``BENCHMARK.json``. A later PR
adds a cell by adding files and entries; nothing here names a cell, a
configuration, a traffic mix or a metric."""

from __future__ import annotations

import importlib
import json
import os

from . import traffic as traffic_mod

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def load_benchmark(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench, name):
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json "
                     f"(have {[w['name'] for w in bench['workloads']]})")


def load_config(root, bench, name):
    for c in bench["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as f:
                cfg = json.load(f)
            cfg["sizes"] = sizes(cfg)
            return cfg
    raise SystemExit(f"perfbench: no config {name!r} in BENCHMARK.json")


def load_traffic(name, bench_dir=HERE):
    return traffic_mod.load(os.path.join(bench_dir, "traffic", name + ".json"))


def sizes(cfg):
    """Every key of the configuration file, and beside it each key that the
    file's ``key_map`` (one common name -> the source's own) gives a second
    name. Nothing is filtered: a reference or a work counter of a new
    family reads whatever keys its file has (experts, latent ranks)."""
    out = {k: v for k, v in cfg.items() if k != "sizes"}
    for common, own in cfg.get("key_map", {}).items():
        out[common] = cfg[own]
    return out


def cell_metrics(bench, cell, section):
    """The metrics of ``section`` this cell reports."""
    out = []
    for mt in bench[section]:
        if "workloads" in mt and cell["name"] not in mt["workloads"]:
            continue
        out.append(mt)
    return out


def load_metric_reader(name, bench_dir=HERE):
    """``perfbench/metrics/<name>.json`` -> its dict. The file names the
    reader (a function of ``readers.py``, or ``module:function`` for a
    reader a later PR brings as a file of its own) and its arguments."""
    with open(os.path.join(bench_dir, "metrics", name + ".json")) as f:
        return json.load(f)


def resolve_reader(spec):
    kind = spec["reader"]
    if ":" in kind:
        mod, fn = kind.split(":")
        return getattr(importlib.import_module(mod), fn)
    from . import readers
    return getattr(readers, "read_" + kind)


def reference_module(cfg):
    return importlib.import_module("perfbench.reference." + cfg["reference"])


# ---- the system under test --------------------------------------------------

def program_config(cfg):
    """The program's own config object, from preset + the file's values."""
    p = cfg["program"]
    mod = importlib.import_module(p["module"])
    pc = getattr(getattr(mod, p["config_class"]), p["preset"])()
    for k, v in p["config"].items():
        if isinstance(v, str) and v.startswith("$"):
            v = cfg[v[1:]]
        if not hasattr(pc, k):
            raise SystemExit(f"perfbench: {p['config_class']} has no field {k}")
        setattr(pc, k, v)
    return pc


def build_model(cfg):
    p = cfg["program"]
    mod = importlib.import_module(p["module"])
    return getattr(mod, p["model_class"])(program_config(cfg))
