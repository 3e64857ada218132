"""What a run is made of, found by name.

`BENCHMARK.json` names cells, configurations and metrics; each has a data
file of its own under `benchmarks/` that this module finds by that name. No
cell's, configuration's, mix's or metric's name appears in code: a later PR
adds one by adding files and one entry, and edits nothing that is here.

    benchmarks/configs/<config>.json        a deployment: schema, sizes, env
    benchmarks/traffic/<traffic>.json       parameters of one generator
    benchmarks/layer_metrics/<metric>.json  which reader, with what arguments
    benchmarks/generators/<generator>.py    run(ctx) -> window record
    benchmarks/readers/<reader>.py          read(sources, **args) -> number
    benchmarks/references/<reference>.py    the plain reference of a config
    benchmarks/datasets/<dataset>.py        what a deployment's rows and queries
                                            carry beside their vectors: the
                                            rows' properties, each pool query's
                                            filter, and which rows a filter
                                            allows (datasets/buckets.py says
                                            what a dataset is)
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmarks/
ROOT = os.path.dirname(HERE)                                        # the checkout

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# a configuration without a `dataset` key has the rows every configuration
# had before datasets were files: one int property, no filter a query
DEFAULT_DATASET = "buckets"


class SpecError(Exception):
    pass


def check_name(name: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"not a name: {name!r}")
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(f"not a unit: {unit!r}")
    return unit


def _load_json(path: str) -> dict:
    with open(path, "rb") as f:
        return json.loads(f.read())


def _load_module(path: str, kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    """`BENCHMARK.json` plus the files it names. Files are looked for under
    `extra_root` first (a test's throw-away set of configs/, traffic/, ...),
    then under benchmarks/."""

    def __init__(self, benchmark_json: str | None = None,
                 extra_root: str | None = None):
        self.path = benchmark_json or os.path.join(ROOT, "BENCHMARK.json")
        self.roots = ([extra_root] if extra_root else []) + [HERE]
        self.doc = _load_json(self.path)
        self.run_seconds = int(self.doc["run_seconds"])
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}
        self.workloads = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}

    # -- cells ---------------------------------------------------------------

    def workload(self, name: str) -> dict:
        if name not in self.workloads:
            raise SpecError(f"no workload {name!r} in {self.path} "
                            f"(known: {sorted(self.workloads)})")
        return self.workloads[name]

    def metrics_for(self, workload: str, which: str) -> list[dict]:
        """The cell's `end_to_end` or `per_layer` metrics: those with no
        `workloads` list, or with this cell in it; a per-layer metric only
        where the metric it moves is reported."""
        table = self.end_to_end if which == "end_to_end" else self.per_layer
        out = [m for m in table.values()
               if "workloads" not in m or workload in m["workloads"]]
        if which == "per_layer":
            e2e = {m["name"] for m in self.metrics_for(workload, "end_to_end")}
            out = [m for m in out if m["moves"] in e2e]
        return out

    # -- files found by name -------------------------------------------------

    def _find(self, kind: str, name: str, ext: str) -> str:
        check_name(name)
        for root in self.roots:
            path = os.path.join(root, kind, name + ext)
            if os.path.isfile(path):
                return path
        raise SpecError(f"no {kind}/{name}{ext} under {self.roots}")

    def as_args(self) -> list[str]:
        """This spec, for a child process's command line."""
        return ["--benchmark-json", self.path] + (
            ["--extra-root", self.roots[0]] if len(self.roots) > 1 else [])

    def config(self, name: str) -> dict:
        """The configuration as it is run (the file `configs[].file` names,
        else configs/<name>.json), with `_sha256` of the file's bytes."""
        entry = self.configs.get(name, {})
        rel = entry.get("file")
        if rel:   # relative to BENCHMARK.json, which is at the checkout's root
            path = os.path.join(os.path.dirname(self.path), rel)
        else:
            path = self._find("configs", name, ".json")
        with open(path, "rb") as f:
            raw = f.read()
        cfg = json.loads(raw)
        cfg["_sha256"] = hashlib.sha256(raw).hexdigest()
        cfg.setdefault("name", name)
        return cfg

    def traffic(self, name: str) -> dict:
        t = _load_json(self._find("traffic", name, ".json"))
        t.setdefault("name", name)
        return t

    def layer_metric(self, name: str) -> dict:
        return _load_json(self._find("layer_metrics", name, ".json"))

    def _module(self, kind: str, name: str):
        return _load_module(self._find(kind, name, ".py"), kind, name)

    def generator(self, name: str):
        return self._module("generators", name)

    def reader(self, name: str):
        return self._module("readers", name)

    def reference(self, name: str):
        return self._module("references", name)

    def dataset(self, cfg: dict):
        """The dataset module of a configuration (its `dataset` key)."""
        return self._module("datasets", cfg.get("dataset", DEFAULT_DATASET))

    # -- the whole set loads and every name passes the rule ------------------

    def validate(self) -> None:
        for w in self.doc["workloads"]:
            check_name(w["name"])
            if w["chips"] not in (1, 4):
                raise SpecError(f"{w['name']}: chips {w['chips']}")
            cfg = self.config(check_name(w["config"]))
            if int(cfg["chips"]) != int(w["chips"]):
                raise SpecError(f"{w['name']}: cell asks {w['chips']} chips, "
                                f"config {cfg['chips']}")
            self.reference(cfg["reference"])
            dataset = self.dataset(cfg)
            for fn in ("properties", "filter_plan", "allowed"):
                if not hasattr(dataset, fn):
                    raise SpecError(f"dataset of {cfg['name']} has no {fn}()")
            traffic = self.traffic(check_name(w["traffic"]))
            self.generator(traffic["generator"])
            names = {m["name"] for m in self.metrics_for(w["name"], "end_to_end")}
            if "setup_s" not in names or len(names) < 2:
                raise SpecError(f"{w['name']}: needs setup_s and one more "
                                f"end-to-end metric, has {sorted(names)}")
            if not self.metrics_for(w["name"], "per_layer"):
                raise SpecError(f"{w['name']}: no per-layer metric")
        for c in self.doc["configs"]:
            check_name(c["name"])
            for key in c["reduced"]:
                check_name(key)
        for m in self.doc["end_to_end"] + self.doc["per_layer"]:
            check_name(m["name"])
            check_unit(m["unit"])
            if m["better"] not in ("lower", "higher"):
                raise SpecError(f"{m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                raise SpecError(f"{m['name']}: source {m['source']!r}")
            for w in m.get("workloads", ()):
                self.workload(w)
        for m in self.doc["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                raise SpecError(f"{m['name']}: an end-to-end metric is taken "
                                "by the benchmark itself")
        for m in self.doc["per_layer"]:
            if m["moves"] not in self.end_to_end:
                raise SpecError(f"{m['name']}: moves {m['moves']!r}")
            f = self.layer_metric(m["name"])
            for key in ("name", "unit", "layer", "moves", "better", "source"):
                if f[key] != m[key]:
                    raise SpecError(
                        f"layer_metrics/{m['name']}.json {key} {f[key]!r} "
                        f"differs from BENCHMARK.json's {m[key]!r}")
            if not hasattr(self.reader(f["reader"]), "read"):
                raise SpecError(f"reader {f['reader']} has no read()")
