"""BENCHMARK.json against the rules a benchmark manifest keeps."""

import importlib
import json
import re

import pytest

from run import BENCH, ROOT, cell_metrics, metric_reader

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def test_top_level_keys_and_paths():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["paths"] == ["bench"]
    assert all(PATH.match(p) and ".." not in p for p in MAN["paths"])
    assert MAN["command"][1].startswith("bench/")
    for f in BENCH.rglob("*"):
        if f.is_file() and "__pycache__" not in f.parts:
            assert PATH.match(str(f.relative_to(ROOT))), f


def test_names_and_units_use_allowed_characters():
    names = [m["name"] for m in METRICS] + [w["name"] for w in MAN["workloads"]]
    names += [c["name"] for c in MAN["configs"]]
    names += [w["traffic"] for w in MAN["workloads"]]
    names += [k for c in MAN["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    assert len({w["name"] for w in MAN["workloads"]}) == len(MAN["workloads"])
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        if m["name"].split(".")[0].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_end_to_end_bounds_and_setup():
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = [m for m in MAN["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]


def test_run_seconds_fit_a_full_check():
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (MAN["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert 1 <= MAN["run_seconds"] <= 51 and total <= 43200


@pytest.mark.parametrize("metric", [m["name"] for m in MAN["per_layer"]])
def test_per_layer_metric_moves_one_reported_metric(metric):
    m = next(x for x in MAN["per_layer"] if x["name"] == metric)
    e2e = {x["name"]: x for x in MAN["end_to_end"]}
    assert m["moves"] in e2e and m["moves"] != "setup_s"
    for wl in m["workloads"]:
        assert wl in e2e[m["moves"]].get("workloads", [wl])
        assert metric in [x["name"] for x in cell_metrics(MAN, wl, True)]
    assert callable(metric_reader(metric))
    layers = {x["layer"] for x in MAN["per_layer"]}
    assert all("\n" not in layer and 0 < len(layer) <= 200 for layer in layers)


@pytest.mark.parametrize("wl", [w["name"] for w in MAN["workloads"]])
def test_every_cell_reports_setup_another_metric_and_a_layer(wl):
    e2e = [m["name"] for m in cell_metrics(MAN, wl, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell_metrics(MAN, wl, True)
    w = next(x for x in MAN["workloads"] if x["name"] == wl)
    assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in e2e:
        assert callable(metric_reader(m))


@pytest.mark.parametrize("config", [c["name"] for c in MAN["configs"]])
def test_config_file_states_its_source_and_cuts(config):
    entry = next(c for c in MAN["configs"] if c["name"] == config)
    assert entry["file"] == f"bench/configs/{config}.json"
    cfg = json.loads((ROOT / entry["file"]).read_text())
    for key in ("source", "reduced", "assumed", "departures", "reference", "limits"):
        assert cfg.get(key), key
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    # every key changed from the source is listed, and its published value kept
    assert set(cfg["source_values"]) == set(entry["reduced"])
    assert all(cfg[k] != v for k, v in cfg["source_values"].items())
    assert (BENCH / "references" / f"{cfg['reference']}.py").is_file()
    assert any(w["config"] == config for w in MAN["workloads"])


@pytest.mark.parametrize("config", [c["name"] for c in MAN["configs"]])
def test_config_family_exposes_the_hooks(config):
    """Every configuration's architecture family, named by its ``reference``
    key, exists beside its reference and exposes every hook the harness
    calls."""
    import families

    cfg = json.loads((ROOT / f"bench/configs/{config}.json").read_text())
    assert (BENCH / "families" / f"{cfg['reference']}.py").is_file()
    fam = importlib.import_module(f"families.{cfg['reference']}")
    for hook in families.HOOKS:
        assert hasattr(fam, hook), hook
    assert all(callable(getattr(fam, h)) for h in families.HOOKS if h != "TINY")
    assert isinstance(fam.TINY, dict)
    att = fam.attention(cfg)
    assert set(att) == {"layers", "heads", "qk_head_dim", "v_head_dim", "seq_len", "causal"}
    assert fam.flops_per_pair(cfg) > 0
