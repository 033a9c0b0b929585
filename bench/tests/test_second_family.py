"""A configuration of another architecture plugs in with files of its own.

The family, its plain reference and its configuration live under
``data/``; the test reaches them by extending the ``families`` and
``references`` packages' search paths, adds two cells of it to the manifest
the run reads and its configuration to what ``cell.load_json`` finds, and
runs the cells whole on the CPU through the unchanged harness.
"""

import json
from pathlib import Path

import pytest

import cell
import run
from conftest import tiny

DATA = Path(__file__).with_name("data")
PROOF = "proof.rms-swiglu-gqa"
# the proof's cells report what the BERT-base cells of the same mix report
MIXES = {"bulk": "zeshel-yugioh.bert-base.bulk",
         "index-build": "zeshel-yugioh.bert-base.index-build"}


@pytest.fixture
def second_family(monkeypatch):
    import families
    import references

    for pkg in (families, references):
        monkeypatch.setattr(pkg, "__path__", [*pkg.__path__, str(DATA / pkg.__name__)])
    man = run.manifest()
    for mix, twin in MIXES.items():
        man["workloads"].append({"name": f"{PROOF}.{mix}", "config": PROOF,
                                 "traffic": mix, "chips": 1, "why": "test"})
        for m in man["end_to_end"] + man["per_layer"]:
            if twin in m.get("workloads", []):
                m["workloads"].append(f"{PROOF}.{mix}")
    monkeypatch.setattr(run, "manifest", lambda: man)
    load = cell.load_json

    def load_json(kind, name):
        if (kind, name) == ("configs", PROOF):
            return json.loads((DATA / "configs" / f"{PROOF}.json").read_text())
        return load(kind, name)

    monkeypatch.setattr(cell, "load_json", load_json)


@pytest.mark.parametrize("mix, kind, metric", [
    ("bulk", "closed", "qps"),
    ("index-build", "index_build", "build_pairs_per_s"),
])
def test_second_family_runs_correct(second_family, mix, kind, metric):
    result = run.run(f"{PROOF}.{mix}", 2**33 + 11, 2.0, False, require_tpu=False,
                     overrides=tiny(kind, PROOF), compile_cache=False,
                     control=mix == "index-build")
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"][metric]["value"] > 0
    if "control" in result:
        # the family's reference in float8 put in the program's place fails
        assert not result["control"]["correct"], result["control"]


def test_second_family_hooks(second_family):
    cfg = cell.load_json("configs", PROOF)
    fam = cell.family(cfg)
    lm = cell.lm_config(cfg)
    assert (lm.n_heads, lm.n_kv_heads, lm.resolved_head_dim) == (8, 4, 32)
    assert (lm.norm, lm.act, lm.qk_norm, lm.causal) == ("rmsnorm", "swiglu", True, False)
    # per token and layer: 256 x 32 x (16 + 8) + 3 x 256 x 1024 + 2 x 64 x 8 x 32
    # = 196,608 + 786,432 + 32,768 MACs; x 2 x 4 layers x 64 tokens
    assert fam.flops_per_pair(cfg) == 2.0 * 1015808 * 4 * 64
    assert fam.attention(cfg) == dict(layers=4, heads=8, qk_head_dim=32, v_head_dim=32,
                                      seq_len=64, causal=False)
