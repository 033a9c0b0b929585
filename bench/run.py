#!/usr/bin/env python3
"""Benchmark of the served ADACUR path on TPU.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json``: builds its data and weights on the
device from the seed, warms every program the cell's traffic reaches, then
drives the program for one measured window and checks what it served
against the plain references in ``bench/references``.  With ``--trace 0``
the result line carries the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics, read from a profiler trace of the window.

Everything is found by name: ``bench/configs/<config>.json`` and the
architecture family its ``reference`` key names, ``bench/families/<reference>.py``,
``bench/traffic/<traffic>.json`` and the kind it names,
``bench/traffic/<kind>.py``, ``bench/metrics/<metric>.py``,
``bench/kernels/<kernel>.py`` and ``bench/peaks.json``.  The run refuses
any platform but a TPU, and a device kind not in the peak table.  The last
line of standard output is one JSON object; the last lines of standard
error are the numbers compared, each beside its limit.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def fail(msg: str, code: int = 2):
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(code)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    for stem in (name, name.split(".", 1)[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.is_file():
            return load_module(path).read
    raise SystemExit(f"no reader for metric {name} under bench/metrics")


def kernel_cost(kernel: str):
    return load_module(BENCH / "kernels" / f"{kernel}.py")


def manifest() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        fail(f"{path.name} is missing")
    return json.loads(path.read_text())


def cell_metrics(man: dict, workload: str, trace: bool) -> list:
    """The metrics this cell reports: end-to-end ones, or per-layer ones
    with ``--trace 1``."""
    e2e = [m for m in man["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in man["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"] in names else [])]


@dataclass
class RunContext:
    """What a metric reader may read."""

    workload: str
    cfg: dict
    traffic: dict
    window: object                  # generator.Window
    setup_s: float
    peaks: dict
    flops_per_pair: float
    engine: dict                    # k_q, n_items, k_s, rounds, k_r, tile, layers ...
    trace: Optional[dict] = None    # trace.reduce() of the window
    trace_pairs: int = 0            # CE pairs the device computed while traced
    trace_batches: list = field(default_factory=list)   # buckets traced
    kernel_cost: object = staticmethod(kernel_cost)


def device_info(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} device_kind={info['kind']} "
          f"count={info['count']}", flush=True)
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if require_tpu:
        if info["platform"] != "tpu":
            fail(f"JAX found no TPU (platform {info['platform']}); this "
                 "benchmark runs only on a TPU")
        if info["count"] < chips:
            fail(f"the cell needs {chips} chips, JAX sees {info['count']}")
        if info["kind"] not in peaks:
            fail(f"device kind {info['kind']!r} is not in bench/peaks.json")
    return info, peaks.get(info["kind"], next(iter(peaks.values())))


class CompileCounter:
    """Counts compilations (tracing, lowering, backend compiles) while on,
    and, all the time, backend compiles and persistent-cache hits."""

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        self.backend_compiles = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.count += self.on
            self.backend_compiles += "backend_compile" in event

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"


def memory_peak(chips: int) -> int:
    """The peak on the fullest chip: the allocator's peak of buffers in use
    plus its peak reserved for programs' temporaries (on a TPU the
    temporaries are reserved apart and left out of ``peak_bytes_in_use``).
    The statistics are printed in full beside it."""
    import jax

    def peak(st):
        return st.get("peak_bytes_in_use", 0) + st.get("peak_bytes_reserved", 0)

    stats = max((d.memory_stats() or {} for d in jax.devices()[:chips]), key=peak)
    print(f"memory statistics of the fullest chip: {json.dumps(stats)}", flush=True)
    return int(peak(stats))


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, overrides: Optional[dict] = None,
        compile_cache: bool = True, control: bool = False) -> dict:
    """One run of one cell; returns the result object.  ``overrides`` merge
    into the configuration and traffic (the tests run cells small on the
    CPU, with ``require_tpu`` and ``compile_cache`` off).  ``control`` adds
    the control's verdict and checks on the same sample, under the key
    ``control`` (``bench/control.py``)."""
    man = manifest()
    wl = next((w for w in man["workloads"] if w["name"] == workload), None)
    if wl is None:
        fail(f"no workload {workload!r} in BENCHMARK.json")
    if compile_cache:
        # the program's entry points take the cache directory from here
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    import numpy as np

    info, peaks = device_info(wl["chips"], require_tpu)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    from repro.launch.compile_cache import enable_compile_cache

    import cell as cell_mod
    import check
    import generator as gen
    from xplane import load as load_trace, reduce as reduce_trace

    if compile_cache:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCounter()

    def phase(what: str) -> None:
        print(f"setup {time.monotonic() - T_PROCESS:8.3f} s: {what} (backend "
              f"compiles {compiles.backend_compiles}, cache hits "
              f"{compiles.cache_hits})", flush=True)

    phase("jax up, device checked")

    cfg = cell_mod.merge(cell_mod.load_json("configs", wl["config"]),
                         (overrides or {}).get("config"))
    traffic = cell_mod.merge(cell_mod.load_json("traffic", wl["traffic"]),
                             (overrides or {}).get("traffic"))
    kind = gen.traffic_kind(traffic["kind"])
    c = cell_mod.build(cfg, traffic, seed, log=phase)
    gen.time_callbacks(c.scorer)
    rng = np.random.default_rng(seed)
    kind.warm(c, traffic, rng, phase)
    jax.effects_barrier()
    phase("warmed")
    setup_s = time.monotonic() - T_PROCESS
    print(f"setup: {setup_s:.3f} s (process start to window start)", flush=True)

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    win = gen.measure(c, traffic, rng, seconds, compiles, tdir)
    summary = None
    if trace:
        path = next(Path(tdir).rglob("*.xplane.pb"))
        summary = reduce_trace(*load_trace(str(path)))
        shutil.rmtree(tdir, ignore_errors=True)
    peak = memory_peak(wl["chips"])
    lag = [r.submitted - r.due for r in win.requests]
    if lag:
        print(f"generator lag (submit - due, includes time the synchronous "
              f"service held the loop): mean {np.mean(lag) * 1e3:.3f} ms, "
              f"p95 {np.percentile(lag, 95) * 1e3:.3f} ms, max "
              f"{np.max(lag) * 1e3:.3f} ms over {len(lag)} requests", flush=True)
    lat = [(r.done - r.due) * 1e3 for r in win.in_window() if r.done is not None]
    if lat:
        print("latency from due time (ms): " + ", ".join(
            f"p{q} {np.percentile(lat, q):.3f}" for q in (50, 90, 95, 99)), flush=True)
    print(f"window: {win.seconds:.3f} s; compilations inside it: "
          f"{compiles.count}; {gen.stall_report(win)}", flush=True)

    eng = check.engine_shape(cfg, c)
    # CE pairs the device computed while traced: the scorer counts served
    # pairs (padding included); bulk_score counts nothing, so its calls do
    traced_pairs = (sum(call[2] for call in win.calls) if win.calls
                    else win.ce_pairs)
    ctx = RunContext(
        workload, cfg, traffic, win, setup_s, peaks,
        cell_mod.family(cfg).flops_per_pair(cfg), eng, summary,
        traced_pairs, [b.bucket for b in win.batches if b.t1 <= win.t_end],
    )
    metrics = {}
    for m in cell_metrics(man, workload, trace):
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # the program's state goes before the references run on the chip
    checks, attempted, failed, ctl = check.run_checks(
        c, win, eng, compiles.count, rng, control)
    correct = check.verdict(checks)
    device = dict(info, count=wl["chips"], memory_peak_bytes=peak)
    if summary is not None:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    if ctl is not None:
        result["control"] = {"correct": check.verdict(ctl), "checks": ctl}
    result["checks"] = checks
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, v in result["checks"].items():
        ok = "ok" if v["value"] <= v["limit"] else "FAIL"
        print(f"check {name} {v['value']!r} <= {v['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
