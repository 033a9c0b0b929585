"""Builds one benchmark cell: the configuration, its data and weights made
on the device from the seed, and the program's objects under test.

Everything the benchmark hands the program is made here, by the
benchmark, so the references in ``bench/references`` can use the same
arrays without taking anything the program made.
"""

from __future__ import annotations

import argparse
import copy
import importlib
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no {kind[:-1]} file {path.relative_to(BENCH.parent)}")
    return json.loads(path.read_text())


def merge(base: dict, over: Optional[dict]) -> dict:
    """``base`` with ``over`` merged in, nested dicts key by key."""
    out = copy.deepcopy(base)
    for k, v in (over or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def seed_key(seed: int):
    """A JAX key from a seed of any size (seeds may exceed 32 bits)."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def family(cfg: dict):
    """The configuration's architecture family (``families/<reference>.py``),
    found by the same name as its plain reference."""
    return importlib.import_module(f"families.{cfg['reference']}")


def lm_config(cfg: dict):
    """The program's model configuration for a benchmark config file."""
    return family(cfg).program_config(cfg)


def make_weights(lm_cfg, cfg: dict, key):
    """Random weights in the program's layout and serving dtypes, made on
    the device in one jitted call."""
    import jax

    from repro.models import cross_encoder

    init_leaf = family(cfg).init_leaf
    shapes = jax.eval_shape(
        lambda: cross_encoder.init_cross_encoder(jax.random.PRNGKey(0), lm_cfg)[0]
    )
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
             for p, _ in flat]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(tree, [
            init_leaf(path, k, s.shape, s.dtype, cfg)
            for path, k, (_, s) in zip(paths, keys, flat)
        ])

    return build(key)


def make_tables(cfg: dict, key):
    """(item tokens (N, Li) on device, query tokens (Q, Lq) on host,
    R_anc (k_q, N) float32 on device), each made in one jitted call."""
    import jax
    import jax.numpy as jnp

    dep, ra = cfg["deployment"], cfg["r_anc"]
    lo, hi = cfg["first_ordinary_token_id"], cfg["vocab_size"]
    k_items, k_queries, k_r = jax.random.split(key, 3)
    n, k_q, rank = dep["n_items"], dep["k_q"], ra["rank"]

    items = jax.jit(lambda k: jax.random.randint(
        k, (n, dep["item_len"]), lo, hi, jnp.int32))(k_items)
    queries = jax.jit(lambda k: jax.random.randint(
        k, (dep["n_queries"], dep["query_len"]), lo, hi, jnp.int32))(k_queries)

    @jax.jit
    def r_anc(k):
        ku, kv, ke = jax.random.split(k, 3)
        s = 1.0 / (1.0 + jnp.arange(rank, dtype=jnp.float32) / 4.0)
        u = jax.random.normal(ku, (k_q, rank)) * s
        v = jax.random.normal(kv, (rank, n))
        low = jnp.matmul(u, v, precision=jax.lax.Precision.HIGHEST) / np.sqrt(rank)
        return low + ra["noise"] * jax.random.normal(ke, (k_q, n))

    return items, np.asarray(queries), r_anc(k_r)


def serving_config(cfg: dict):
    """The engine configuration the serving CLI builds for these settings."""
    from repro.launch.serve import serving_config as cli_config

    eng = cfg["engine"]
    cfg_engine = cli_config(argparse.Namespace(
        budget=eng["budget"], rounds=eng["rounds"], scorer="real-ce",
        fused=eng["fused"], payload_dtype=eng["payload_dtype"],
        round_kernel=eng["round_kernel"],
    ))
    want = dict(k_anchor=eng["k_anchor"], k_retrieve=eng["k_retrieve"],
                strategy=eng["strategy"], pinv_rcond=eng["pinv_rcond"])
    got = {k: getattr(cfg_engine, k) for k in want}
    if got != want:
        raise SystemExit(f"the serving CLI's engine config {got} is not the "
                         f"configuration's {want}")
    return cfg_engine


@dataclass
class Cell:
    """One cell's data, weights and the program objects under test."""

    cfg: dict
    traffic: dict
    seed: int
    lm_cfg: Any
    params: Any
    item_tokens: Any                 # (N, Li) int32, device
    query_tokens: np.ndarray         # (Q, Lq) int32, host
    r_anc: Any                       # (k_q, N) float32, device
    scorer: Any = None               # repro DeviceCEScorer
    service: Any = None              # repro AdaCURService (serving cells)
    engine_cfg: Any = None

    def free_program(self) -> None:
        """Drop the program's objects; the benchmark's arrays stay."""
        self.service = None
        self.scorer = None


def build(cfg: dict, traffic: dict, seed: int, log=lambda what: None) -> Cell:
    """The cell's weights (from the configuration's ``weight_seed``: the
    program compiles them into its engine, so they stay fixed across runs)
    and its corpus, queries and R_anc (from ``seed``)."""
    import jax

    lm_cfg = lm_config(cfg)
    params = make_weights(lm_cfg, cfg, seed_key(cfg["weight_seed"]))
    jax.block_until_ready(params)
    log("weights made")
    items, queries, r_anc = make_tables(cfg, seed_key(seed))
    jax.block_until_ready(r_anc)
    log("corpus, queries and R_anc made")
    cell = Cell(cfg, traffic, seed, lm_cfg, params, items, queries, r_anc)
    from generator import traffic_kind
    from repro.core.scorer import DeviceCEScorer

    serving = traffic_kind(traffic["kind"]).SERVICE
    cell.scorer = DeviceCEScorer(
        params, lm_cfg, query_token_fn=lambda q: cell.query_tokens[q],
        item_tokens=None if serving else items, **family(cfg).scorer_kwargs(cfg),
    )
    if serving:
        _build_service(cell, seed)
    return cell


def _build_service(cell: Cell, seed: int) -> None:
    from repro.core.engine import AdaCURRetriever
    from repro.core.index import AnchorIndex
    from repro.launch.serve import AdaCURService

    svc_cfg = cell.cfg["service"]
    cell.engine_cfg = serving_config(cell.cfg)
    index = AnchorIndex.from_r_anc(cell.r_anc).with_item_tokens(cell.item_tokens)
    retriever = AdaCURRetriever.from_index(index, cell.scorer, cell.engine_cfg)
    cell.service = AdaCURService(
        retriever=retriever, max_batch=svc_cfg["max_batch"],
        max_wait_s=svc_cfg["max_wait_s"], seed=seed & 0x7FFFFFFF,
        batch_buckets=svc_cfg["batch_buckets"],
    )
    if cell.service.batch_buckets != sorted(svc_cfg["batch_buckets"]):
        raise SystemExit("service buckets differ from the configuration's")


def reseed(cell: Cell, seed: int) -> None:
    """New corpus, queries and R_anc from ``seed`` under the same weights
    and compiled programs: the index is swapped into the service (or the
    table into the bulk scorer), shapes unchanged, so nothing recompiles."""
    from repro.core.index import AnchorIndex

    cell.item_tokens = cell.query_tokens = cell.r_anc = None
    items, queries, r_anc = make_tables(cell.cfg, seed_key(seed))
    cell.item_tokens, cell.query_tokens, cell.r_anc, cell.seed = items, queries, r_anc, seed
    if cell.service is not None:
        cell.service.swap_index(AnchorIndex.from_r_anc(r_anc).with_item_tokens(items))
    else:
        cell.scorer.item_tokens = items
