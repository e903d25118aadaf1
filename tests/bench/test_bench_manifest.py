"""BENCHMARK.json against the benchmark's rules, and the harness finding
a configuration, a traffic mix and a per-layer metric by name alone."""
import copy
import json
import os
import subprocess
import sys
import time

import jax
import pytest

from conftest import ROOT
from bench.lib import manifest

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_manifest_keeps_the_rules():
    assert manifest.problems(MAN) == []
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51


def test_every_name_resolves_to_its_files():
    for c in MAN["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert set(c["reduced"]) <= set(conf)
        assert any(c["file"].startswith(p + "/") for p in MAN["paths"])
    for w in MAN["workloads"]:
        cell = manifest.cell(ROOT, w["name"])
        assert cell["config"]["chips"] == w["chips"]
        manifest.driver(ROOT, cell["traffic"]["driver"])
    for m in MAN["per_layer"]:
        assert callable(manifest.reader(ROOT, m["name"]))


def _broken(edit):
    man = copy.deepcopy(MAN)
    edit(man)
    return manifest.problems(man)


@pytest.mark.parametrize("edit, says", [
    (lambda m: m["workloads"][0].update(name="has space"), "bad name"),
    (lambda m: m["end_to_end"][0].update(unit="reads per s"), "bad unit"),
    (lambda m: m["per_layer"][0].update(moves="sim_events_per_s"),
     "does not report"),
    (lambda m: [w.update(chips=4) for w in m["workloads"]], "4 chips"),
    (lambda m: m["workloads"].append(dict(m["workloads"][0], name="dup")),
     "pair appears twice"),
    (lambda m: m["end_to_end"].pop(), "setup_s"),
])
def test_problems_are_found(edit, says):
    assert any(says in p for p in _broken(edit))


def test_new_config_mix_and_metric_by_files_alone(tiny_root):
    """A cell added as new files plus new manifest entries: no file the
    benchmark already has is edited."""
    bench = tiny_root / "bench"
    conf = json.loads((bench / "configs" / "lease-kv-8x16384.json")
                      .read_text())
    conf.update(name="lease-kv-extra", replica_ways=4)
    (bench / "configs" / "lease-kv-extra.json").write_text(
        json.dumps(conf))
    mix = json.loads((bench / "traffic" / "ycsbc-96.hit.json").read_text())
    mix.update(keys=8, chunk_reads=128, chunks=1)
    (bench / "traffic" / "extra-mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "extra_waves.py").write_text(
        "def read(ctx):\n    return ctx['counters']['waves']\n")
    man = json.loads((tiny_root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "lease-kv-extra", "source": "test",
                           "file": "bench/configs/lease-kv-extra.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "extra.cell",
                             "config": "lease-kv-extra",
                             "traffic": "extra-mix", "chips": 1,
                             "why": "test"})
    for m in man["end_to_end"]:
        if m["name"] == "reads_per_s":
            m["workloads"].append("extra.cell")
    man["per_layer"].append({"name": "extra_waves", "unit": "waves",
                             "better": "lower", "source": "program_counter",
                             "layer": "scheduler", "moves": "reads_per_s",
                             "workloads": ["extra.cell"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(man))
    assert manifest.problems(man) == []
    from bench.run import run_cell

    kw = dict(seed=3, seconds=0.2, devices=jax.devices(),
              t0=time.perf_counter())
    line, _ = run_cell(tiny_root, "extra.cell", trace=0, **kw)
    assert line["correct"] and set(line["metrics"]) == {"reads_per_s",
                                                        "setup_s"}
    line, info = run_cell(tiny_root, "extra.cell", trace=1, **kw)
    assert line["correct"]
    assert line["metrics"]["extra_waves"]["value"] == info["waves"] > 0


def _cli(cwd, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kv-ycsbc-96.hit",
         "--seed", "5", "--seconds", "1", *extra], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    out = _cli(ROOT)
    assert out.returncode != 0 and out.stdout == ""
    assert "needs a TPU" in out.stderr


def test_refuses_without_the_program(tmp_path):
    """A directory holding only the manifest and the benchmark's files."""
    import shutil

    for p in MAN["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = _cli(tmp_path)
    assert out.returncode != 0 and out.stdout == ""
