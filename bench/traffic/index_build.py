"""Offline index build: ``bulk_score`` over ``queries`` anchor queries
times consecutive blocks of ``items_per_call`` items, one call after the
other.  The anchor queries and the first block come from the seed."""

import time

import numpy as np

from generator import span

SERVICE = False    # the cell builds no service


def schedule(rng, n_queries: int, n_items: int, queries: int):
    """(anchor query ids, first item of the first block)."""
    qids = np.sort(rng.choice(n_queries, size=queries, replace=False))
    return qids, int(rng.integers(n_items))


def warm(c, traffic, rng, log):
    import jax
    import jax.numpy as jnp

    qids = np.arange(traffic["queries"]) % c.cfg["deployment"]["n_queries"]
    jax.block_until_ready(c.scorer.bulk_score(qids, jnp.arange(traffic["items_per_call"])))
    log("bulk_score warmed")


def drive(c, win, rng, traffic, seconds):
    import jax
    import jax.numpy as jnp

    dep = c.cfg["deployment"]
    queries, per_call, n_items = traffic["queries"], traffic["items_per_call"], dep["n_items"]
    qids, start = schedule(rng, dep["n_queries"], n_items, queries)
    win.t_start = time.monotonic()
    deadline = win.t_start + seconds
    block = 0
    while True:
        lo = (start + block * per_call) % n_items
        ids = (lo + np.arange(per_call)) % n_items
        t0 = time.monotonic()
        with span(win, "bench.bulk_score"):
            out = jax.block_until_ready(c.scorer.bulk_score(qids, jnp.asarray(ids)))
        t1 = time.monotonic()
        win.calls.append((t0, t1, queries * per_call, qids, ids, out))
        block += 1
        if t1 >= deadline:
            win.t_end = t1
            return
