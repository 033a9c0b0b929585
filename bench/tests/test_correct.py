"""``correct`` comes out false for the control and for a broken program.

Each test drives a whole run of a cell at a small size on the CPU (the
look for a chip skipped), with the program broken underneath from the
start, so the fault is compiled into the timed path.
"""

import pytest

import run
from conftest import tiny

BULK = "zeshel-yugioh.bert-base.bulk"
OPEN = "hotpotqa.minilm-l6.poisson-20"
BUILD = "zeshel-yugioh.bert-base.index-build"


def _run(workload, seed=2**33 + 3, control=False):
    config, mix = workload.rsplit(".", 1)
    kind = {"bulk": "closed", "poisson-20": "poisson", "index-build": "index_build"}[mix]
    return run.run(workload, seed, 2.0, False, require_tpu=False,
                   overrides=tiny(kind, config), compile_cache=False, control=control)


def _failing(result):
    return sorted(k for k, v in result["checks"].items() if v["value"] > v["limit"])


def test_run_refuses_a_host_without_tpu():
    with pytest.raises(SystemExit) as e:
        run.run(BULK, 1, 1.0, False, overrides=tiny("closed", "zeshel-yugioh.bert-base"),
                compile_cache=False)
    assert e.value.code != 0


@pytest.mark.parametrize("workload", [BULK, OPEN, BUILD])
def test_sound_program_is_correct(workload):
    result = _run(workload)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("workload", [BULK, BUILD])
def test_control_reads_far_above_the_program(workload):
    """The reference in float8 put in the program's place comes out not
    correct by the cell's own limits, on the same sample, and reads at
    least three times what the bfloat16 program reads (the limit itself
    is set from chip readings at the cell's own size, see PERF.md)."""
    result = _run(workload, control=True)
    prog = {k: v["value"] for k, v in result["checks"].items()}
    ctl = {k: v["value"] for k, v in result["control"]["checks"].items()}
    assert result["correct"], result["checks"]
    assert not result["control"]["correct"], result["control"]
    assert ctl["ce_gap"] > result["control"]["checks"]["ce_gap"]["limit"]
    assert ctl["ce_gap"] >= 3 * prog["ce_gap"], (prog, ctl)


def _break_scores(monkeypatch):
    """A CE score altered where it is produced."""
    from repro.core.scorer import DeviceCEScorer

    orig = DeviceCEScorer._score_flat
    monkeypatch.setattr(DeviceCEScorer, "_score_flat",
                        lambda self, t: orig(self, t) + 1.0)


def _half_batch(monkeypatch):
    """Half of each CE batch left out: its rows take the first row's scores."""
    from repro.core.scorer import DeviceCEScorer

    orig = DeviceCEScorer._score_flat

    def half(self, t):
        s = orig(self, t)
        keep = s.shape[0] // 2
        return s.at[keep:].set(s[0])

    monkeypatch.setattr(DeviceCEScorer, "_score_flat", half)


def _stale_state(monkeypatch):
    """The CUR state update returns its state unchanged."""
    from repro.core import cur

    monkeypatch.setattr(cur, "block_pinv_extend_static",
                        lambda a, p, b, start, ridge=1e-8: p)


def _wrong_ids(monkeypatch):
    """An answer altered where it is produced: served ids shifted by one."""
    from repro.core.index import AnchorIndex

    orig = AnchorIndex.gather_item_ids
    monkeypatch.setattr(AnchorIndex, "gather_item_ids",
                        lambda self, pos: (orig(self, pos) + 1) % self.n_items)


@pytest.mark.parametrize("workload, fault, caught_by", [
    (BULK, _break_scores, "ce_gap"),
    (BULK, _half_batch, "ce_gap"),
    (BULK, _stale_state, "cur_gap"),
    (OPEN, _wrong_ids, "topk_faults"),
    (BUILD, _break_scores, "ce_gap"),
    (BUILD, _half_batch, "ce_gap"),
])
def test_fault_makes_run_incorrect(monkeypatch, workload, fault, caught_by):
    fault(monkeypatch)
    result = _run(workload)
    assert not result["correct"]
    assert caught_by in _failing(result), result["checks"]
