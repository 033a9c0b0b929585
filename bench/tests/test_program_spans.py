"""The per-layer metrics read from the program's own spans and marks
(``bench/program_spans.py``): reported by a traced run, and in agreement
with the harness's own timing of the same searches."""

import pytest

import generator
import program_spans
import run
from conftest import tiny

CELLS = {
    "zeshel-yugioh.bert-base.bulk": ("closed", "bulk",
                                     ["flush_host_ms", "round_ms", "rerank_ms"]),
    "hotpotqa.minilm-l6.poisson-20": ("poisson", "open",
                                      ["flush_host_ms", "batch_wait_ms",
                                       "round_ms", "rerank_ms"]),
}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_traced_run_reports_program_span_metrics(workload, monkeypatch):
    kind, suffix, names = CELLS[workload]
    windows = []
    measure = generator.measure

    def kept(*a, **kw):
        windows.append(measure(*a, **kw))
        return windows[-1]

    monkeypatch.setattr(generator, "measure", kept)
    result = run.run(workload, 2**33 + 5, 3.0, True, require_tpu=False,
                     overrides=tiny(kind, workload.rsplit(".", 1)[0]),
                     compile_cache=False)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    for name in names:
        assert metrics[f"{name}.{suffix}"] > 0, (name, metrics)

    # inside and outside views of one search: dispatch to the first mark,
    # R - 1 rounds and the rerank add up to the harness's search time
    fs = program_spans.flushes(windows[0])
    assert fs and all(len(f.marks) == f.rounds + 1 for f in fs)
    rounds = fs[0].rounds
    assert all(f.rounds == rounds for f in fs)
    first = sum(f.marks[0] - f.dispatch_t0 for f in fs) / len(fs) * 1e3
    inside = (first + (rounds - 1) * metrics[f"round_ms.{suffix}"]
              + metrics[f"rerank_ms.{suffix}"])
    outside = metrics[f"search_ms.{suffix}"]
    assert abs(inside - outside) <= max(0.1 * outside, 5.0), (inside, outside)


def test_nothing_to_read_without_the_recorder(monkeypatch):
    """On a program without ``repro.core.telemetry`` the readers find
    nothing and the metrics are left out, without an error."""
    import sys

    import repro.core

    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    win = generator.Window(t_start=0.0, t_end=float("inf"))
    assert program_spans.flushes(win) == []
    for name in ("flush_host_ms", "batch_wait_ms", "round_ms", "rerank_ms"):
        assert run.metric_reader(f"{name}.open")(type("Ctx", (), {"window": win})) is None
