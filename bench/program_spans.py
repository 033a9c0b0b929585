"""The program's own records (``repro.core.telemetry``), grouped by service
flush, for the readers of the ``program_span`` metrics.

A flush is a ``serve.flush`` span that starts inside the window, with its
children ``engine.dispatch`` and ``serve.device_wait``.  Its CE-round marks
are the ``ce.round`` marks inside its dispatch-to-ready interval, from the
start of ``engine.dispatch`` to the end of ``serve.device_wait``.  The host
waits for the device from the end of ``engine.dispatch`` to the end of
``serve.device_wait``: the harness's ``instrument`` wraps the retriever's
``search`` in a ``block_until_ready``, so under the benchmark most of the
wait falls between the two spans.  A program without the recorder leaves
nothing to read.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass
from typing import List


@dataclass
class Flush:
    t0: float                  # serve.flush start
    t1: float                  # serve.flush end
    arrival_t: list            # hand-over times of its real requests
    rounds: int                # adaptive rounds the engine ran
    dispatch_t0: float         # engine.dispatch start
    dispatch_t1: float         # engine.dispatch end
    ready: float               # serve.device_wait end
    marks: List[float]         # ce.round marks, in time order


def flushes(win) -> List[Flush]:
    """The window's flushes that ran the engine and ended in a response."""
    try:
        from repro.core import telemetry
    except ImportError:
        return []
    rec = telemetry.snapshot()
    kids: dict = {}
    for s in rec.spans:
        if s.parent is not None:
            kids.setdefault(s.parent, {})[s.name] = s
    times = [m.t for m in rec.marks if m.name == "ce.round"]
    out = []
    for f in rec.spans:
        if f.name != "serve.flush" or not win.t_start <= f.t0 <= win.t_end:
            continue
        k = kids.get(f.id, {})
        d, w = k.get("engine.dispatch"), k.get("serve.device_wait")
        if d is None or w is None or f.attrs.get("rounds") is None:
            continue
        marks = times[bisect.bisect_left(times, d.t0):bisect.bisect_right(times, w.t1)]
        out.append(Flush(f.t0, f.t1, f.attrs["arrival_t"], f.attrs["rounds"],
                         d.t0, d.t1, w.t1, marks))
    return out
