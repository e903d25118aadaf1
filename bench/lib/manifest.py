"""The benchmark's manifest (``BENCHMARK.json``) and the files it names.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by name:

  * a configuration: the ``file`` its manifest entry names;
  * a traffic mix ``<mix>``: ``bench/traffic/<mix>.json``, which names a
    driver ``bench/drivers/<driver>.py``;
  * a per-layer metric ``<metric>``: ``bench/metrics/<metric>.py``, or
    where there is none, ``bench/metrics/<stem>.py`` for the part of the
    name before its first dot (one reader for ``host_us_per_read.p99``
    and ``host_us_per_read.rps``).
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH_DIR = "bench"


def load(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def cell(root: Path, workload: str) -> dict:
    """Everything one cell needs: its manifest entry, configuration,
    traffic mix and the metrics it reports."""
    root = Path(root)
    man = load(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; one of "
                       f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    reports = lambda m: workload in m.get("workloads", [workload])
    return {
        "workload": w,
        "config": json.loads((root / conf["file"]).read_text()),
        "traffic": json.loads(
            (root / BENCH_DIR / "traffic" / f"{w['traffic']}.json")
            .read_text()),
        "end_to_end": [m for m in man["end_to_end"] if reports(m)],
        "per_layer": [m for m in man["per_layer"] if reports(m)],
    }


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(root: Path, name: str):
    path = Path(root) / BENCH_DIR / "drivers" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no driver {path}")
    return _module(path, f"bench_driver_{name}")


def reader(root: Path, metric: str):
    """The ``read(ctx)`` function of a per-layer metric."""
    base = Path(root) / BENCH_DIR / "metrics"
    for stem in (metric, metric.split(".")[0]):
        path = base / f"{stem}.py"
        if path.is_file():
            return _module(path, "bench_metric_" +
                           re.sub(r"\W", "_", stem)).read
    raise FileNotFoundError(f"no reader for metric {metric!r} in {base}")


def problems(man: dict) -> list:
    """What in a manifest breaks the benchmark's rules on names, units,
    directions, cells and the end-to-end metric each per-layer one moves;
    an empty list when nothing does."""
    out = []
    names = lambda xs: [x["name"] for x in xs]
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = names(man[kind])
        if len(set(ns)) != len(ns):
            out.append(f"{kind}: duplicate names")
        out += [f"{kind}: bad name {n!r}" for n in ns if not NAME.match(n)]
    metrics = man["end_to_end"] + man["per_layer"]
    if len(set(names(metrics))) != len(metrics):
        out.append("a name is both an end-to-end and a per-layer metric")
    for m in metrics:
        if not UNIT.match(m["unit"]):
            out.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            out.append(f"{m['name']}: better is {m['better']!r}")
    cells = {w["name"]: w for w in man["workloads"]}
    confs = set(names(man["configs"]))
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    if len(set(pairs)) != len(pairs):
        out.append("a (config, traffic) pair appears twice")
    for w in man["workloads"]:
        if w["config"] not in confs:
            out.append(f"{w['name']}: unknown config {w['config']!r}")
        if not NAME.match(w["traffic"]):
            out.append(f"{w['name']}: bad traffic name")
        if w["chips"] not in (1, 4):
            out.append(f"{w['name']}: chips {w['chips']}")
    unused = confs - {w["config"] for w in man["workloads"]}
    out += [f"config {c!r} is used by no cell" for c in sorted(unused)]
    four = sum(w["chips"] == 4 for w in man["workloads"])
    if four > max(1, len(cells) // 2):
        out.append(f"{four} of {len(cells)} cells ask for 4 chips")
    e2e = {m["name"]: m for m in man["end_to_end"]}
    if "setup_s" not in e2e:
        out.append("no setup_s")
    reported = {c: {n for n, m in e2e.items()
                    if c in m.get("workloads", cells)} for c in cells}
    for c, ms in reported.items():
        if "setup_s" not in ms or len(ms) < 2:
            out.append(f"{c}: reports no end-to-end metric but setup_s")
    for m in man["per_layer"]:
        if m["moves"] not in e2e:
            out.append(f"{m['name']}: moves unknown {m['moves']!r}")
            continue
        for c in m.get("workloads", cells):
            if c not in cells:
                out.append(f"{m['name']}: unknown cell {c!r}")
            elif m["moves"] not in reported[c]:
                out.append(f"{m['name']}: {c} does not report "
                           f"{m['moves']}")
    for c in cells:
        if not any(c in m.get("workloads", cells) for m in man["per_layer"]):
            out.append(f"{c}: no per-layer metric")
    return out
