"""BENCHMARK.json against the benchmark's contract, and the harness finding
a configuration, a mix and a metric by name alone."""
import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import generate, manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


def test_top_level_keys(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= man["run_seconds"] <= 51
    assert 1 <= len(man["paths"]) <= 16
    for p in man["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert len(man["command"]) <= 32
    assert os.path.isfile(os.path.join(ROOT, man["command"][1]))


def test_names_and_units(man):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in man[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [n for g, n in names if g == group]
        assert len(got) == len(set(got))
    metric_names = [n for g, n in names if g in ("end_to_end", "per_layer")]
    assert len(metric_names) == len(set(metric_names))
    for w in man["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200


def test_entry_keys(man):
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in man["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in man["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_cell_reports_what_it_must(man):
    cells = {w["name"] for w in man["workloads"]}
    for w in cells:
        e2e = {m["name"] for m in manifest.end_to_end(man, w)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert manifest.per_layer(man, w)
    for m in man["per_layer"]:
        for w in m["workloads"]:
            assert w in cells
            assert m["moves"] in {e["name"]
                                  for e in manifest.end_to_end(man, w)}, \
                (m["name"], w)


def test_metric_files_agree_with_the_manifest(man):
    layers = {}
    for m in man["per_layer"]:
        mod = manifest.metric(ROOT, m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"],
                                                   m["moves"])
        assert callable(mod.read)
        layers.setdefault(m["layer"].split(" ")[0], set()).add(m["layer"])
    # metrics of one layer give its name letter for letter
    assert all(len(v) == 1 for v in layers.values())
    for m in man["per_layer"]:
        if m["name"].split(".")[0].endswith("_roofline") or \
                m["name"].startswith("mfu"):
            assert m["unit"] == "%"


def test_config_files(man):
    files = [c["file"] for c in man["configs"]]
    assert len(files) == len(set(files))
    for c in man["configs"]:
        conf = manifest.config(ROOT, man, c["name"])
        for key in ("source", "reduced", "assumed", "deployment", "model",
                    "serving", "check", "reference"):
            assert key in conf, (c["name"], key)
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        changed = {k for k, v in conf["published"].items()
                   if conf["model"].get(k) != v}
        assert changed == set(conf["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in
                       c["reduced"])
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "configs", conf["reference"] + ".py"))


def test_mixes(man):
    for w in man["workloads"]:
        mix = manifest.mix(ROOT, w["traffic"])
        (plo, phi), (olo, ohi) = generate.length_ranges(mix)
        assert phi + ohi <= mix["max_len"]
        assert mix["kind"] in ("closed", "poisson")
        assert mix["check_requests"] >= 1


def test_found_by_name_alone(tmp_path):
    """A throwaway configuration, mix and per-layer metric in a copy of the
    benchmark are found by name, with no harness code edited."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = manifest.load(ROOT)
    conf = manifest.config(ROOT, man, "yi-9b.s12")
    conf["name"] = "scratch.s1"
    with open(os.path.join(root, "bench", "configs", "scratch.s1.json"),
              "w") as f:
        json.dump(conf, f)
    mix = dict(manifest.mix(ROOT, "decode-long"), name="scratch-mix",
               kind="poisson", rate=3)
    with open(os.path.join(root, "bench", "traffic", "scratch-mix.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "bench", "metrics", "scratch_ms.cell.py"),
              "w") as f:
        f.write('LAYER = "device"\nUNIT = "ms"\nMOVES = "ttft_p95_ms"\n\n\n'
                'def read(run):\n    return 1.5\n')
    man["configs"].append({"name": "scratch.s1", "source": "x",
                           "file": "bench/configs/scratch.s1.json",
                           "reduced": [], "why": "x"})
    man["workloads"].append({"name": "scratch.cell", "config": "scratch.s1",
                             "traffic": "scratch-mix", "chips": 1,
                             "why": "x"})
    man["end_to_end"].append({"name": "ttft_p95_ms", "unit": "ms",
                              "better": "lower", "bound": 0.1,
                              "source": "host_clock",
                              "workloads": ["scratch.cell"]})
    man["per_layer"].append({"name": "scratch_ms.cell", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "device", "moves": "ttft_p95_ms",
                             "workloads": ["scratch.cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    got = manifest.load(root)
    cell = manifest.cell(got, "scratch.cell")
    assert manifest.config(root, got, cell["config"])["name"] == "scratch.s1"
    assert manifest.mix(root, cell["traffic"])["rate"] == 3
    assert [m["name"] for m in manifest.per_layer(got, "scratch.cell")] == \
        ["scratch_ms.cell"]
    assert manifest.metric(root, "scratch_ms.cell").read({}) == 1.5
    assert {m["name"] for m in manifest.end_to_end(got, "scratch.cell")} == \
        {"ttft_p95_ms", "setup_s"}
    assert manifest.reference(root, conf).score
