"""Reduces a profiler trace (``.xplane.pb``) to device busy and idle time,
kernel time, and the breakdown a result line carries.

Device planes are those named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per operation the device ran, named by its HLO instruction
text (``%flash_attention.3 = bf16[...] custom-call(...)``).  An operation's
base name is the instruction's name without its number
(``flash_attention``).  Loops and calls (``while``) enclose their bodies'
events and are left out.  Busy time is the union of the remaining events
inside the measured window, averaged over the devices; the window is the host span
``bench.window`` that the harness opens around it.  Idle gaps are charged
to the innermost ``bench.*`` host span open at their midpoint.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
# ops whose events enclose their bodies' ops: counted through those ops
CONTAINERS = {"while", "conditional", "call"}
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def base_name(event_name: str) -> str:
    """``%flash_attention.19 = bf16[...] custom-call(...)`` ->
    ``flash_attention``: the HLO instruction's name without its number."""
    op = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", op)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _profile(path: str):
    """A trace file, gzipped (``.gz``, as kept with the tests) or not."""
    import gzip

    import jax

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return jax.profiler.ProfileData.from_serialized_xspace(f.read())
    return jax.profiler.ProfileData.from_file(str(path))


def load(path: str):
    """(device ops per device: [(start_ns, end_ns, name)], host spans:
    [(start_ns, end_ns, name)]) from an ``.xplane.pb`` file."""
    data = _profile(path)
    devices, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops += [(ev.start_ns, ev.start_ns + ev.duration_ns, name)
                            for ev in line.events
                            if (name := base_name(ev.name)) not in CONTAINERS]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                          for ev in line.events if ev.name.startswith(SPAN_PREFIX)]
    return devices, spans


def _innermost(spans, times) -> List[str]:
    """For each of the ascending ``times``, the name of the latest-starting
    span (spans sorted by start) that contains it."""
    out, open_, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            open_.append(spans[i])
            i += 1
        open_ = [s for s in open_ if s[1] >= t]
        out.append(open_[-1][2] if open_ else "no bench span")
    return out


def reduce(devices, spans, top: int = 10) -> Optional[Dict]:
    """Busy and idle seconds, per-op device seconds and the breakdown over
    the ``bench.window`` span (or, without one, the trace's extent)."""
    ops_all = [op for ops in devices for op in ops]
    if not ops_all:
        return None
    win = [(a, b) for a, b, n in spans if n == WINDOW_SPAN]
    if win:
        w0, w1 = win[0]
    else:
        w0 = min(a for a, _, _ in ops_all)
        w1 = max(b for _, b, _ in ops_all)
    window_ns = w1 - w0
    per_op: Dict[str, float] = defaultdict(float)
    busy_ns = []
    gaps_by_span: Dict[str, float] = defaultdict(float)
    inner = sorted((a, b, n) for a, b, n in spans if n != WINDOW_SPAN)
    for ops in devices:
        clipped = [(max(a, w0), min(b, w1), n) for a, b, n in ops if b > w0 and a < w1]
        for a, b, n in clipped:
            per_op[n] += (b - a) * 1e-9
        busy = _union([(a, b) for a, b, _ in clipped])
        busy_ns.append(sum(b - a for a, b in busy))
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for (a, b), name in zip(gaps, _innermost(inner, [0.5 * (a + b) for a, b in gaps])):
            gaps_by_span[name] += (b - a) * 1e-9 / len(devices)
    busy_s = sum(busy_ns) / len(busy_ns) * 1e-9
    n_dev = len(devices)
    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": busy_s,
        "window_s": window_ns * 1e-9,
        "op_seconds": {k: v / n_dev for k, v in per_op.items()},
        "device_ops": [[k, v / n_dev] for k, v in ops_sorted[:top]],
        "idle_gaps": [[k, v] for k, v in
                      sorted(gaps_by_span.items(), key=lambda kv: -kv[1])[:top]],
    }


def kernel_seconds(summary: Dict, kernel: str) -> float:
    """Device seconds of the ops named ``kernel`` (a Pallas kernel's
    ``name``) in the window."""
    return summary["op_seconds"].get(kernel, 0.0)
