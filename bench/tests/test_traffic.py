"""The same seed gives the same inputs; another seed gives other ones."""

import numpy as np
import pytest

import cell
import generator

POISSON = generator.traffic_kind("poisson")
INDEX_BUILD = generator.traffic_kind("index_build")


@pytest.mark.parametrize("seed", [0, 2**33 + 17])
def test_poisson_schedule_is_seeded(seed):
    a = POISSON.schedule(np.random.default_rng(seed), 4096, 10.0, 45)
    b = POISSON.schedule(np.random.default_rng(seed), 4096, 10.0, 45)
    c = POISSON.schedule(np.random.default_rng(seed + 1), 4096, 10.0, 45)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert len(a[0]) > 10.0 * 45 and np.all(a[1] < 4096)


def test_build_schedule_is_seeded():
    a = INDEX_BUILD.schedule(np.random.default_rng(5), 4096, 10031, 100)
    b = INDEX_BUILD.schedule(np.random.default_rng(5), 4096, 10031, 100)
    assert np.array_equal(a[0], b[0]) and a[1] == b[1]
    assert len(set(a[0].tolist())) == 100


def test_tables_and_weights_are_seeded():
    from conftest import tiny

    cfg = cell.merge(cell.load_json("configs", "zeshel-yugioh.bert-base"),
                     tiny("closed", "zeshel-yugioh.bert-base")["config"])
    lm = cell.lm_config(cfg)
    one = [cell.make_tables(cfg, cell.seed_key(2**32 + 9)),
           cell.make_weights(lm, cfg, cell.seed_key(2**32 + 9))]
    two = [cell.make_tables(cfg, cell.seed_key(2**32 + 9)),
           cell.make_weights(lm, cfg, cell.seed_key(2**32 + 9))]
    other = cell.make_tables(cfg, cell.seed_key(9))
    import jax

    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(two)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(one[0][2]), np.asarray(other[2]))
    items, queries, r_anc = one[0]
    lo, hi = cfg["first_ordinary_token_id"], cfg["vocab_size"]
    assert items.shape == (600, 16) and queries.shape == (64, 13)
    assert int(items.min()) >= lo and int(items.max()) < hi
    assert r_anc.shape == (cfg["deployment"]["k_q"], 600)
