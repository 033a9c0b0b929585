#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's numbers and the
control's, over many seeds, in one process.

    python bench/control.py --workload <name> --seconds <s> --seeds 1 2 3 ...

Builds the cell once, then for each seed makes a new corpus, query table
and R_anc (``cell.reseed``: same weights and compiled programs), drives
the cell's own traffic for a short window through the benchmark's own
window runner (``generator.measure``), and compares what the program served
with the references, as ``run.py`` does.  On the same sample it also
puts the control in the program's place: the CE forward with float8
operands (below the configuration's bfloat16) and the CUR solve at
``high`` precision (below float32 at HIGHEST), and judges it by the same
limits.  One JSON line per seed: ``{"seed", "attempted", "correct",
"checks", "control": {"correct", "checks"}}``, each check a value beside
its limit.  Not part of a benchmark run.
"""

import argparse
import json

import numpy as np

import run


def _stale_state():
    """The CUR state update returns its state unchanged."""
    from repro.core import cur

    cur.block_pinv_extend_static = lambda a, p, b, start, ridge=1e-8: p


FAULTS = {"stale_state": _stale_state}


def main(argv=None, overrides=None, require_tpu=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), default=None,
                    help="plant a fault in the program before it compiles")
    args = ap.parse_args(argv)
    man = run.manifest()
    wl = next(w for w in man["workloads"] if w["name"] == args.workload)
    run.os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    run.device_info(wl["chips"], require_tpu)
    run.sys.path.insert(0, str(run.ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    import cell as cell_mod
    import check
    import generator as gen

    enable_compile_cache()
    if args.fault:
        FAULTS[args.fault]()
    compiles = run.CompileCounter()
    cfg = cell_mod.merge(cell_mod.load_json("configs", wl["config"]),
                         (overrides or {}).get("config"))
    traffic = cell_mod.merge(cell_mod.load_json("traffic", wl["traffic"]),
                             (overrides or {}).get("traffic"))
    c = cell_mod.build(cfg, traffic, args.seeds[0])
    gen.traffic_kind(traffic["kind"]).warm(
        c, traffic, np.random.default_rng(args.seeds[0]), lambda what: None)
    for seed in args.seeds:
        cell_mod.reseed(c, seed)
        rng = np.random.default_rng(seed)
        win = gen.measure(c, traffic, rng, args.seconds, compiles)
        service, scorer = c.service, c.scorer
        checks, attempted, _, ctl = check.run_checks(
            c, win, check.engine_shape(cfg, c), compiles.count, rng, control=True)
        c.service, c.scorer = service, scorer
        print(json.dumps({"seed": seed, "attempted": attempted,
                          "correct": check.verdict(checks), "checks": checks,
                          "control": {"correct": check.verdict(ctl), "checks": ctl}}),
              flush=True)


if __name__ == "__main__":
    main()
