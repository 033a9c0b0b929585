"""AnchorIndex lifecycle benchmark -> ``BENCH_index.json``.

Measures the offline side of the system end to end:

- **build**: block-streamed R_anc scoring throughput (scores/s) through the
  resumable builder, plus the warm-resume time (pure block reload — what a
  preempted pod-scale job pays on restart);
- **latents / save / load**: ANNCUR precompute and persistence round-trip
  on the Checkpointer machinery, with a bit-parity check of
  save -> load -> topk against the in-memory index;
- **mutate**: add_items/remove_items wall time (capacity-padded, no
  retrace);
- **sharded-search parity**: ``shard(mesh)`` over all local devices must
  produce the identical top-k to the unsharded index (shard_map fused
  per-shard top-k + cross-shard merge).

    PYTHONPATH=src python -m benchmarks.index_build [--fast]
"""

from __future__ import annotations

import argparse
import json
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import AnchorIndex
from repro.launch.mesh import make_mesh

from .common import emit


def _timer():
    t0 = time.perf_counter()
    return lambda: time.perf_counter() - t0


def run(
    n_items: int = 10000,
    k_q: int = 200,
    block_rows: int = 64,
    capacity_headroom: int = 256,
    json_path: str = "BENCH_index.json",
    quiet: bool = False,
):
    from repro.data.synthetic import make_synthetic_ce

    ce = make_synthetic_ce(jax.random.PRNGKey(0), n_queries=k_q, n_items=n_items + capacity_headroom)
    capacity = n_items + capacity_headroom
    work = tempfile.mkdtemp(prefix="bench_index_")
    ck_dir, save_dir = f"{work}/build_ckpt", f"{work}/saved"
    snapshot = {"n_items": n_items, "k_q": k_q, "block_rows": block_rows,
                "capacity": capacity}
    try:
        # -- build (cold) + resume (warm) -----------------------------------
        t = _timer()
        index = AnchorIndex.build(
            ce.score_block, jnp.arange(k_q), jnp.arange(n_items),
            block_rows=block_rows, checkpoint_dir=ck_dir, capacity=capacity,
        )
        build_s = t()
        t = _timer()
        AnchorIndex.build(
            ce.score_block, jnp.arange(k_q), jnp.arange(n_items),
            block_rows=block_rows, checkpoint_dir=ck_dir, capacity=capacity,
        )
        resume_s = t()
        scores_per_s = k_q * n_items / build_s
        snapshot["build"] = {
            "build_s": round(build_s, 3),
            "resume_s": round(resume_s, 3),
            "scores_per_s": round(scores_per_s, 1),
        }
        emit("index_build/build", build_s * 1e6,
             f"scores_per_s={scores_per_s:.0f};resume_s={resume_s:.3f}")

        # -- latents + save/load round trip ---------------------------------
        t = _timer()
        index = index.with_latents(k_anchor=100, key=jax.random.PRNGKey(2))
        latents_s = t()
        t = _timer()
        index.save(save_dir)
        save_s = t()
        t = _timer()
        loaded = AnchorIndex.load(save_dir)
        load_s = t()
        e_q = jax.random.normal(jax.random.PRNGKey(3), (8, k_q))
        v0, i0 = jax.block_until_ready(index.topk(e_q, 100))
        v1, i1 = jax.block_until_ready(loaded.topk(e_q, 100))
        save_load_parity = bool(
            (np.asarray(i0) == np.asarray(i1)).all()
            and np.allclose(np.asarray(v0), np.asarray(v1))
        )
        snapshot["persistence"] = {
            "latents_s": round(latents_s, 3),
            "save_s": round(save_s, 3),
            "load_s": round(load_s, 3),
            "save_load_parity": save_load_parity,
        }
        emit("index_build/save", save_s * 1e6, f"load_s={load_s:.3f};parity={save_load_parity}")

        # -- mutation (padded capacity, no reshape) --------------------------
        new_ids = jnp.arange(n_items, n_items + capacity_headroom // 2)
        t = _timer()
        grown = index.add_items(new_ids, bulk_score_fn=ce.score_block)
        jax.block_until_ready(grown.r_anc)
        add_s = t()
        # removable = any valid items that are not ANNCUR anchors
        anchor_ids = np.asarray(grown.gather_item_ids(grown.anchor_item_pos))
        removable = np.setdiff1d(np.arange(n_items), anchor_ids)[:64]
        t = _timer()
        shrunk = grown.remove_items(jnp.asarray(removable))
        jax.block_until_ready(shrunk.r_anc)
        remove_s = t()
        snapshot["mutation"] = {
            "add_items_s": round(add_s, 4),
            "remove_items_s": round(remove_s, 4),
            "added": int(new_ids.shape[0]),
            "removed": 64,
        }
        emit("index_build/mutate", (add_s + remove_s) * 1e6,
             f"add_s={add_s:.4f};remove_s={remove_s:.4f}")

        # -- sharded-search parity over all local devices --------------------
        mesh = make_mesh((jax.device_count(),), ("data",))
        sharded = index.shard(mesh)
        t = _timer()
        vs, is_ = jax.block_until_ready(sharded.topk(e_q, 100))
        shard_topk_s = t()
        cap = sharded.capacity
        # account for shard()'s divisibility re-pad: compare vs the same capacity
        ref = index if cap == index.capacity else index.with_capacity(cap)
        vr, ir = jax.block_until_ready(ref.topk(e_q, 100))
        sharded_parity = bool(
            (np.asarray(is_) == np.asarray(ir)).all()
            and np.allclose(np.asarray(vs), np.asarray(vr), rtol=1e-5, atol=1e-6)
        )
        snapshot["sharded"] = {
            "n_devices": jax.device_count(),
            "topk_s": round(shard_topk_s, 4),
            "sharded_search_parity": sharded_parity,
        }
        emit("index_build/sharded_topk", shard_topk_s * 1e6,
             f"devices={jax.device_count()};parity={sharded_parity}")
    finally:
        shutil.rmtree(work, ignore_errors=True)

    if json_path:
        with open(json_path, "w") as fh:
            json.dump(snapshot, fh, indent=2)
        if not quiet:
            print(f"# wrote {json_path}")
    return snapshot


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller domain")
    ap.add_argument("--json", default="BENCH_index.json")
    args = ap.parse_args()
    if args.fast:
        run(n_items=2000, k_q=64, block_rows=16, json_path=args.json)
    else:
        run(json_path=args.json)
