"""The program's recorder (``core/telemetry``): span nesting and durations,
the bounded buffers, marks from other threads, and the spans and CE-round
marks one service flush over a device-resident CE leaves, in memory and in
a profiler trace."""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import AdaCURConfig, replace
from repro.core import engine, telemetry
from repro.core.index import AnchorIndex
from repro.core.scorer import DeviceCEScorer
from repro.data.synthetic import make_zeshel_like
from repro.launch.serve import AdaCURService, RetrievalRequest
from repro.models import cross_encoder

FLUSH_SPANS = ["serve.flush", "serve.prepare", "engine.dispatch",
               "engine.tokenize", "serve.device_wait", "serve.respond"]


@pytest.fixture(autouse=True)
def fresh():
    telemetry.reset()
    yield
    telemetry.reset()


def test_span_nesting_parents_and_durations():
    with telemetry.span("outer", n=1) as attrs:
        time.sleep(0.01)
        with telemetry.span("inner"):
            time.sleep(0.02)
        attrs["late"] = [1, 2]
    with telemetry.span("after"):
        pass
    spans = telemetry.snapshot().spans
    assert [s.name for s in spans] == ["outer", "inner", "after"]
    outer, inner, after = spans
    assert outer.parent is None and after.parent is None
    assert inner.parent == outer.id
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1 <= after.t0
    assert inner.t1 - inner.t0 >= 0.02
    assert outer.t1 - outer.t0 >= 0.03
    assert outer.attrs == {"n": 1, "late": [1, 2]}


def test_span_is_recorded_when_its_block_raises():
    with pytest.raises(KeyError):
        with telemetry.span("failing"):
            raise KeyError("x")
    with telemetry.span("next"):
        pass
    spans = telemetry.snapshot().spans
    assert [s.name for s in spans] == ["failing", "next"]
    assert spans[1].parent is None


def test_bounded_buffers_drop_their_oldest_records():
    n = telemetry.MAX_RECORDS + 7
    for i in range(n):
        telemetry.mark("m", i=i)
    for i in range(3):
        with telemetry.span("s", i=i):
            pass
    marks = telemetry.snapshot().marks
    assert len(marks) == telemetry.MAX_RECORDS
    assert marks[0].attrs["i"] == 7 and marks[-1].attrs["i"] == n - 1
    assert len(telemetry.snapshot().spans) == 3
    telemetry.reset()
    assert telemetry.snapshot() == ([], [])


def test_marks_from_other_threads():
    """More marking threads than cores, switching often, while the main
    thread holds a span open and reads snapshots: no record is lost and
    each thread's marks keep their order."""
    n_threads = (os.cpu_count() or 1) + 1
    per_thread = min(500, telemetry.MAX_RECORDS // n_threads - 1)

    def work(k):
        for i in range(per_thread):
            telemetry.mark("ce.round", thread=k, i=i)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with telemetry.span("main"):
            threads = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
            for t in threads:
                t.start()
            while any(t.is_alive() for t in threads):
                telemetry.snapshot()
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    rec = telemetry.snapshot()
    assert len(rec.marks) == n_threads * per_thread
    assert all(a.t <= b.t for a, b in zip(rec.marks, rec.marks[1:]))
    for k in range(n_threads):
        mine = [m.attrs["i"] for m in rec.marks if m.attrs["thread"] == k]
        assert mine == list(range(per_thread))
    (main,) = rec.spans
    assert all(main.t0 <= m.t <= main.t1 for m in rec.marks)


@pytest.fixture(scope="module")
def ce_parts():
    """Tiny transformer CE over a tiny corpus, and anchor scores from it."""
    ds = make_zeshel_like(0, n_items=80, n_queries=24, item_len=12, query_len=8)
    lm = replace(
        registry.CE_TINY, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, vocab_size=ds.vocab_size, dtype="float32",
        remat=False,
    )
    params, _ = cross_encoder.init_cross_encoder(jax.random.PRNGKey(0), lm)
    scorer = DeviceCEScorer(
        params, lm, query_token_fn=lambda q: np.asarray(ds.query_tokens)[q],
        item_tokens=ds.item_tokens, len_buckets=(32, 64), flash_block=(16, 16),
    )
    r_anc = scorer.bulk_score(jnp.arange(16), jnp.arange(80))
    return ds, params, lm, r_anc


def _service(ce_parts, cfg):
    ds, params, lm, r_anc = ce_parts
    scorer = DeviceCEScorer(
        params, lm, query_token_fn=lambda q: np.asarray(ds.query_tokens)[q],
        len_buckets=(32, 64), flash_block=(16, 16),
    )
    index = AnchorIndex.from_r_anc(r_anc).with_item_tokens(ds.item_tokens)
    retriever = engine.AdaCURRetriever.from_index(index, scorer, cfg)
    return AdaCURService(retriever=retriever, max_batch=4, max_wait_s=60.0,
                         batch_buckets=[2, 4])


def _flush(svc, qids):
    """One flush of ``qids`` (a full batch fires from ``submit``)."""
    out = []
    for q in qids:
        out += svc.submit(RetrievalRequest(query_id=int(q))) or []
    return out + svc.flush()


def _children(spans, parent):
    return [s for s in spans if s.parent == parent.id]


@pytest.mark.parametrize("split_budget", [True, False])
def test_service_flush_spans_and_ce_round_marks(ce_parts, split_budget):
    """Each flush leaves serve.flush over prepare, dispatch (over
    tokenize), device_wait and respond, in that order, and one ce.round
    mark per CE scoring call the engine ran inside its dispatch-to-ready
    interval, whose pairs add up to the call plan over the padded bucket."""
    budget = 24 if split_budget else 12
    cfg = AdaCURConfig(k_anchor=12, n_rounds=3, budget_ce=budget,
                       k_retrieve=10, loop_mode="fori", split_budget=split_budget)
    svc = _service(ce_parts, cfg)
    _flush(svc, [16, 17])                  # compiles bucket 2
    _flush(svc, [18, 19, 20])              # compiles bucket 4
    telemetry.reset()
    t_submit = time.monotonic()
    batches = [[16, 17, 18], [19, 20], [21, 22, 23, 16]]
    out = [_flush(svc, b) for b in batches]
    rec = telemetry.snapshot()

    flushes = [s for s in rec.spans if s.name == "serve.flush"]
    assert len(flushes) == 3
    assert [s.name for s in rec.spans] == FLUSH_SPANS * 3
    used = 0
    for f, b, resp in zip(flushes, batches, out):
        assert f.parent is None
        assert f.attrs["n_real"] == len(b)
        assert f.attrs["bucket"] == (2 if len(b) <= 2 else 4)
        assert len(f.attrs["arrival_t"]) == len(b)
        assert all(t_submit <= a <= f.t0 for a in f.attrs["arrival_t"])
        rounds = f.attrs["rounds"]
        assert rounds == resp[0].rounds_completed == cfg.n_rounds
        kids = _children(rec.spans, f)
        assert [k.name for k in kids] == ["serve.prepare", "engine.dispatch",
                                          "serve.device_wait", "serve.respond"]
        prep, disp, wait, respond = kids
        assert f.t0 <= prep.t0 <= prep.t1 <= disp.t0 <= disp.t1 <= wait.t0
        assert wait.t1 <= respond.t0 <= respond.t1 <= f.t1
        (tok,) = _children(rec.spans, disp)
        assert tok.name == "engine.tokenize"
        assert disp.t0 <= tok.t0 <= tok.t1 <= disp.t1
        marks = [m for m in rec.marks if disp.t0 <= m.t <= wait.t1]
        assert all(m.name == "ce.round" for m in marks)
        assert len(marks) == rounds + int(split_budget)
        plan = engine.ce_call_plan(cfg, rounds) * f.attrs["bucket"]
        assert sum(m.attrs["pairs"] for m in marks) == plan
        assert all(m.attrs["pad"] == 0 for m in marks)
        used += len(marks)
    assert used == len(rec.marks)


def test_flush_span_in_profiler_trace(ce_parts, tmp_path):
    """Under jax.profiler, serve.flush and the CE-round marks show on the
    /host:CPU plane of the trace file."""
    cfg = AdaCURConfig(k_anchor=12, n_rounds=3, budget_ce=24, k_retrieve=10,
                       loop_mode="fori")
    svc = _service(ce_parts, cfg)
    _flush(svc, [16, 17])
    jax.profiler.start_trace(str(tmp_path))
    try:
        _flush(svc, [18, 19])
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    names = [ev.name for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events]
    base = [n.split("#", 1)[0] for n in names]
    for name in FLUSH_SPANS:
        assert base.count(name) == 1, (name, sorted(set(base)))
    assert base.count("ce.round") == cfg.n_rounds + 1
