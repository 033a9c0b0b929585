"""Compile the served path's Pallas kernels for one described TPU v5e chip.

Nothing runs: the TPU compiler installed next to JAX compiles each kernel
for one chip of a described ``v5e:2x2`` topology at the served shapes
(batch 16, k_q 100 anchor queries, 10,240 items, float32 / int8 / fp8
payloads; the CE's head_dim 32 in the 256-token bucket), and each test asserts that the executable holds the
compiled kernel (``tpu_custom_call``).  This is what the interpret-mode
tests cannot see: a block shape Mosaic refuses, a primitive it cannot
lower, more VMEM than a core has.

The topology is described inside a module fixture — never at import time —
so every test worker collects the same tests and only the worker given this
file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import AdaCURConfig
from repro.kernels.approx_topk.ops import approx_topk_op
from repro.kernels.approx_topk.persistent import persistent_round_op
from repro.kernels.approx_topk.quant import QuantizedRanc
from repro.kernels.flash_attention.kernel import flash_attention

B, K_Q, N = 16, 100, 10_240
K_SAMPLE, K_RERANK = 20, 100          # per-round anchors, rerank budget
TILE = AdaCURConfig().fused_tile
PAIRS, BUCKET, HEADS, KV_HEADS, HEAD_DIM = 320, 256, 8, 4, 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _payload(sharding, dtype):
    if dtype == "float32":
        return _shape(sharding, (K_Q, N), jnp.float32)
    codes = {"int8": jnp.int8, "int4": jnp.uint8,
             "fp8": jnp.float8_e4m3fn}[dtype]
    width = N // 2 if dtype == "int4" else N
    return QuantizedRanc(
        _shape(sharding, (K_Q, width), codes),
        _shape(sharding, (N // 512,), jnp.float32), 512, dtype,
    )


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_calls(text: str, name: str) -> int:
    return sum(
        'custom_call_target="tpu_custom_call"' in line and f"%{name}" in line
        for line in text.splitlines()
    )


# v5e has no fp8 unit: fp8 codes compile to a software widen in the kernel
@pytest.mark.parametrize("dtype", ["float32", "int8", "fp8"])
@pytest.mark.parametrize("k", [K_SAMPLE, K_RERANK])
def test_staged_sweep_compiles(one_chip, dtype, k):
    def sweep(e_q, r_anc, selected):
        return approx_topk_op(e_q, r_anc, k, tile=TILE, mask=selected,
                              impl="pallas", interpret=False)

    text = _compiled_text(
        sweep, _shape(one_chip, (B, K_Q), jnp.float32),
        _payload(one_chip, dtype), _shape(one_chip, (B, N), jnp.bool_),
    )
    assert _kernel_calls(text, "approx_topk_sweep") == 1


@pytest.mark.parametrize("dtype", ["float32", "fp8"])
def test_persistent_sweep_compiles(one_chip, dtype):
    def sweep(e_q, r_anc, selected, noise):
        return persistent_round_op(
            e_q, r_anc, k_sample=K_SAMPLE, k_prov=K_RERANK, mask=selected,
            prov_mask=selected, noise=noise, tile=TILE, impl="pallas",
            interpret=False,
        )

    text = _compiled_text(
        sweep, _shape(one_chip, (B, K_Q), jnp.float32),
        _payload(one_chip, dtype), _shape(one_chip, (B, N), jnp.bool_),
        _shape(one_chip, (B, N), jnp.float32),
    )
    assert _kernel_calls(text, "persistent_round_sweep") == 1


def test_flash_attention_with_kv_lens_compiles(one_chip):
    def attend(q, k, v, lens):
        return flash_attention(q, k, v, causal=False, block_q=128,
                               block_k=128, kv_lens=lens, interpret=False)

    text = _compiled_text(
        attend,
        _shape(one_chip, (PAIRS, BUCKET, HEADS, HEAD_DIM), jnp.bfloat16),
        _shape(one_chip, (PAIRS, BUCKET, KV_HEADS, HEAD_DIM), jnp.bfloat16),
        _shape(one_chip, (PAIRS, BUCKET, KV_HEADS, HEAD_DIM), jnp.bfloat16),
        _shape(one_chip, (PAIRS,), jnp.int32),
    )
    assert _kernel_calls(text, "flash_attention") == 1


def test_int4_payload_refused_by_name(one_chip):
    """Packed int4 cannot compile for the chip: the sweep says so, naming
    itself and the dtype, instead of handing the work to an emulation."""
    def sweep(e_q, r_anc, selected):
        return approx_topk_op(e_q, r_anc, K_SAMPLE, tile=TILE,
                              mask=selected, impl="pallas", interpret=False)

    with pytest.raises(NotImplementedError, match="approx_topk_sweep: int4"):
        _compiled_text(
            sweep, _shape(one_chip, (B, K_Q), jnp.float32),
            _payload(one_chip, "int4"), _shape(one_chip, (B, N), jnp.bool_),
        )
