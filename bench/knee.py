#!/usr/bin/env python3
"""Finds the highest arrival rate a serving cell sustains (the knee), once,
by a sweep on the chip.  Not part of a benchmark run: its result is the
rate written into the Poisson traffic files.

    python bench/knee.py --workload <serving cell> --seed <n> --seconds <s> \
        --fractions 0.5 0.7 0.8 0.9 1.0 1.1

Builds the cell once, measures the closed-loop capacity (every batch
full), then drives open Poisson loops at each fraction of it and prints,
per rate: completions per second, p50/p95 latency and mean batch fill.
A rate whose completions per second fall short of it builds a backlog:
the knee is the highest rate that does not.
"""

import argparse
import json
import time

import numpy as np

import run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fractions", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    man = run.manifest()
    wl = next(w for w in man["workloads"] if w["name"] == args.workload)
    run.os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    run.device_info(wl["chips"], True)
    run.sys.path.insert(0, str(run.ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    import cell as cell_mod
    import generator as gen

    enable_compile_cache()
    cfg = cell_mod.load_json("configs", wl["config"])
    traffic = cell_mod.load_json("traffic", wl["traffic"])
    c = cell_mod.build(cfg, traffic, args.seed)
    svc = cfg["service"]
    rng = np.random.default_rng(args.seed)
    gen.traffic_kind("poisson").warm(c, traffic, rng, lambda what: None)
    win = gen.measure(c, dict(kind="closed", clients=svc["max_batch"]), rng,
                      args.seconds)
    capacity = len(win.requests) / win.seconds
    batch_s = np.mean([b.t1 - b.t0 for b in win.batches])
    print(json.dumps({"closed_loop_qps": capacity, "full_batch_s": batch_s}), flush=True)
    for frac in args.fractions:
        rate = frac * capacity
        win = gen.measure(c, dict(traffic, kind="poisson", rate_qps=rate), rng,
                          args.seconds)
        lat = [(r.done - r.due) * 1e3 for r in win.in_window()]
        done = [r for r in win.requests if r.done is not None and r.done <= win.t_end]
        print(json.dumps({
            "fraction": frac, "rate_qps": rate, "completed_qps": len(done) / win.seconds,
            "p50_ms": float(np.percentile(lat, 50)), "p95_ms": float(np.percentile(lat, 95)),
            "batch_fill": float(np.mean([b.n_real for b in win.batches])),
            "requests": len(lat),
        }), flush=True)
        time.sleep(1.0)


if __name__ == "__main__":
    main()
