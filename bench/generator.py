"""The one traffic runner: runs one measured window of a cell's traffic.

A traffic file (``bench/traffic/<name>.json``) holds a mix's parameters
and names its ``kind``; the kind is the module ``bench/traffic/<kind>.py``,
found by that name, with ``SERVICE`` (whether its cell builds the
service or only the scorer) and two functions:

- ``warm(cell, traffic, rng, log)``: runs every program shape the kind's
  window will reach (set-up);
- ``drive(cell, win, rng, traffic, seconds)``: offers the load from the
  seed until the window closes, at the first completion at or after
  ``seconds``, and records it in ``win``.

This module holds what the kinds share: the window's records, host
spans, the timing of each engine search, the drain of what was queued
at the close, and ``measure``, which every caller (the benchmark run,
the control readings, the knee sweep) uses to drive a window.
"""

from __future__ import annotations

import gc
import importlib.util
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional

import numpy as np

TRAFFIC = Path(__file__).resolve().parent / "traffic"
_CURRENT: list = [None]          # the window being measured, for the callback stamps


@dataclass
class Batch:
    t0: float                    # search dispatched
    t1: float                    # search results on the host side
    bucket: int                  # batch rows searched, padding included
    result: object               # the engine's result (device arrays)
    n_real: int = 0


@dataclass
class Request:
    qid: int
    due: float
    submitted: float
    done: Optional[float] = None
    ok: bool = False
    batch: Optional[int] = None  # index into Window.batches
    row: int = 0
    ids: Optional[np.ndarray] = None
    scores: Optional[np.ndarray] = None


@dataclass
class Window:
    t_start: float = 0.0
    t_end: float = 0.0
    requests: List[Request] = field(default_factory=list)
    batches: List[Batch] = field(default_factory=list)
    calls: list = field(default_factory=list)      # index_build: (t0, t1, pairs, qids, ids, out)
    spans: list = field(default_factory=list)      # (name, t0, t1)
    gc_pauses: list = field(default_factory=list)  # (generation, t0, t1)
    callbacks: list = field(default_factory=list)  # host time of each CE-round callback
    ce_pairs: int = 0            # CE pairs the scorer counted inside the window
    recording: bool = False

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start

    def in_window(self):
        return [r for r in self.requests if r.due <= self.t_end]


def traffic_kind(kind: str):
    """The module ``bench/traffic/<kind>.py``."""
    path = TRAFFIC / f"{kind}.py"
    if not path.is_file():
        raise SystemExit(f"no traffic kind {kind!r}: bench/traffic/{kind}.py is missing")
    spec = importlib.util.spec_from_file_location(f"traffic_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@contextmanager
def span(win: Window, name: str):
    """A host span: kept in the window's list and, while the profiler runs,
    written into its trace on the device's clock."""
    import jax

    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation(name):
        yield
    if win.recording:
        win.spans.append((name, t0, time.monotonic()))


def instrument(service, win: Window) -> None:
    """Wrap the retriever's ``search`` so each batch the service flushes is
    timed to the end of its device work and its result kept."""
    import jax

    retriever = service.retriever
    search = type(retriever).search.__get__(retriever)

    def timed_search(query, key=None, **kw):
        t0 = time.monotonic()
        with span(win, "bench.search"):
            res = jax.block_until_ready(search(query, key, **kw))
        win.batches.append(Batch(t0, time.monotonic(), len(query), res))
        return res

    retriever.search = timed_search


def time_callbacks(scorer) -> None:
    """Stamp the host time of each CE-round accounting callback into the
    window being measured.  Installed before the engine is traced, since
    the callback is bound at trace time."""
    count = scorer._count_host

    def stamped(idx, n_pad):
        out = count(idx, n_pad)
        win = _CURRENT[0]
        if win is not None and win.recording:
            win.callbacks.append(time.monotonic())
        return out

    scorer._count_host = stamped


def answered(win: Window, responses, queue, now: float) -> int:
    """Match a flushed batch's responses to their requests (in submission
    order)."""
    if not responses:
        return 0
    b = len(win.batches) - 1
    win.batches[b].n_real = len(responses)
    for row, resp in enumerate(responses):
        req = queue.pop(0)
        req.done, req.ok = now, resp.status == "ok"
        req.batch, req.row = b, row
        req.ids, req.scores = resp.item_ids, resp.scores
    return len(responses)


def warm_buckets(service, qid_pool, buckets, log=lambda what: None) -> None:
    """One flush at each bucket the traffic can reach: compiles (or loads
    from the cache) every program the window will run."""
    from repro.launch.serve import RetrievalRequest

    for b in buckets:
        for q in qid_pool[:b]:
            service.submit(RetrievalRequest(query_id=int(q)))
        service.flush()
        log(f"bucket {b} warmed")


def drain(service, win: Window) -> None:
    """Answer what was still queued when the window closed; those requests
    were due inside it, so their latency counts."""
    queue = [r for r in win.requests if r.done is None]
    while queue:
        out = service.flush()
        if not out:
            break
        answered(win, out, queue, time.monotonic())


def measure(c, traffic: dict, rng, seconds: float, compiles=None,
            profile_dir: Optional[str] = None) -> Window:
    """Drives one measured window of the cell's traffic and answers what
    was queued at its close; returns the window's records.  ``compiles``
    (``run.CompileCounter``) counts compilations inside the window;
    ``profile_dir`` records a profiler trace of it."""
    import jax

    kind = traffic_kind(traffic["kind"])
    win = Window()
    _CURRENT[0] = win
    if c.service is not None:
        instrument(c.service, win)

    def on_gc(phase, info):
        if phase == "start":
            on_gc.t0 = time.monotonic()
        elif win.recording:
            win.gc_pauses.append((info["generation"], on_gc.t0, time.monotonic()))

    on_gc.t0 = 0.0
    if profile_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(profile_dir, profiler_options=opts)
    stats0 = c.scorer.stats.copy()
    gc.callbacks.append(on_gc)
    if compiles is not None:
        compiles.on = True
    win.recording = True
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            kind.drive(c, win, rng, traffic, seconds)
    finally:
        win.recording = False
        if compiles is not None:
            compiles.on = False
        gc.callbacks.remove(on_gc)
        if profile_dir is not None:
            jax.profiler.stop_trace()
    win.ce_pairs = c.scorer.stats.ce_calls - stats0.ce_calls
    if c.service is not None:
        drain(c.service, win)
    return win


def stall_report(win: Window) -> str:
    """Where a window's stalls can sit: the slowest engine search, split at
    its CE-round callbacks (dispatch to the first, between them, the last
    to results ready); the service's host work around searches and the
    generator's own steps; the garbage collector's pauses."""
    parts = []
    if win.batches:
        dur = np.array([b.t1 - b.t0 for b in win.batches if b.t0 < win.t_end])
        if len(dur):
            slow = win.batches[int(np.argmax(dur))]
            marks = [t for t in win.callbacks if slow.t0 <= t <= slow.t1]
            steps = np.diff([slow.t0, *marks, slow.t1]) * 1e3
            parts.append(
                f"searches: median {np.median(dur) * 1e3:.3f} ms, slowest "
                f"{dur.max() * 1e3:.3f} ms at {slow.t0 - win.t_start:.3f} s "
                f"(bucket {slow.bucket}; dispatch, callback gaps, ready: "
                f"{' '.join(f'{s:.3f}' for s in steps)} ms)")
    calls = sorted((s for s in win.spans
                    if s[0] in ("bench.submit", "bench.poll", "bench.wait")),
                   key=lambda s: s[1])
    if len(calls) > 1:
        searches = [(t0, t1) for name, t0, t1 in win.spans if name == "bench.search"]
        service = [(t1 - t0) - sum(s1 - s0 for s0, s1 in searches if t0 <= s0 and s1 <= t1)
                   for name, t0, t1 in calls if name != "bench.wait"]
        gaps = [b0 - a1 for (_, _, a1), (_, b0, _) in zip(calls, calls[1:])]
        parts.append(f"host: longest service work outside a search "
                     f"{max(service, default=0.0) * 1e3:.3f} ms, longest generator "
                     f"step between calls {max(gaps) * 1e3:.3f} ms")
    if win.gc_pauses:
        p = np.array([t1 - t0 for _, t0, t1 in win.gc_pauses]) * 1e3
        gen2 = sum(1 for g, _, _ in win.gc_pauses if g == 2)
        parts.append(f"gc: {len(p)} pauses ({gen2} full), total {p.sum():.3f} ms, "
                     f"longest {p.max():.3f} ms")
    return "; ".join(parts)
