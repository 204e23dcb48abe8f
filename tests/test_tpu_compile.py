"""Compile the main path's kernels for a described TPU v5e, no chip needed.

Interpret mode runs a kernel's body on the CPU; it does not check what
Mosaic (the TPU kernel compiler) accepts: block shapes that break the
(8, 128) tiling rule, VMEM overuse, programs that do not fit HBM. These
tests lower and compile each kernel of the serving and allocator paths
at the widths ``chip_smoke.py`` runs: one Mixtral-8x7B layer's KV
(``hkv=8, d=128``), a 262,144-token pool, batch 64, and 1,000 tenants'
8,192-bucket sketches.

The topology is described inside a module-scoped fixture only: loading
the TPU compiler at import time would make pytest-xdist workers collect
different tests. The persistent compilation cache is off here, because
an entry compiled for a described chip cannot be read back without one.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kv_scatter import kv_append_pallas, kv_chunk_copy_pallas
from repro.kernels.sketch_update import sketch_window_pallas
from repro.kernels.slab_attention import slab_decode_attention_pallas
from repro.kernels.waste_eval import (waste_eval_fleet_pallas,
                                     waste_eval_pallas)
from repro.serving.offline_harness import _decode_step_fn, _prefill_step_fn

HKV, D = 8, 128                    # configs/mixtral_8x7b.py KV width
B = 64                             # max_batch
MAX_CHUNK = 1024                   # top slab class, tokens
POOL_ROWS = 262144 + MAX_CHUNK     # pool_tokens + the scatter junk range
VOCAB = 16
TENANT_BUCKETS = 1 << 13           # ControllerConfig.device_buckets
FRONTIER_ROWS, FRONTIER_K, FRONTIER_S = 3000, 32, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compilation_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("g", [1, 4], ids=["mha", "gqa4"])
def test_slab_decode_attention_compiles(one_chip, dtype, g):
    def attend(q, k, v, starts, lens):
        return slab_decode_attention_pallas(q, k, v, starts, lens,
                                            max_chunk_tokens=MAX_CHUNK)

    _compile(attend,
             _spec(one_chip, (B, HKV * g, D), dtype),
             _spec(one_chip, (POOL_ROWS, HKV, D), dtype),
             _spec(one_chip, (POOL_ROWS, HKV, D), dtype),
             _spec(one_chip, (B,), jnp.int32),
             _spec(one_chip, (B,), jnp.int32))


def test_kv_append_compiles(one_chip):
    _compile(lambda pool, rows, vals: kv_append_pallas(pool, rows, vals),
             _spec(one_chip, (POOL_ROWS, HKV, D), jnp.float32),
             _spec(one_chip, (B,), jnp.int32),
             _spec(one_chip, (B, HKV, D), jnp.float32))


def test_kv_chunk_copy_compiles(one_chip):
    def copy(pool, src, dst, n):
        return kv_chunk_copy_pallas(pool, src, dst, n,
                                    max_copy_tokens=MAX_CHUNK)

    _compile(copy,
             _spec(one_chip, (POOL_ROWS, HKV, D), jnp.float32),
             *[_spec(one_chip, (B,), jnp.int32)] * 3)


def test_harness_decode_step_compiles(one_chip):
    step = _decode_step_fn(MAX_CHUNK, VOCAB, False, True, "pallas")
    pool = _spec(one_chip, (POOL_ROWS, HKV, D), jnp.float32)
    vec = _spec(one_chip, (B,), jnp.int32)
    compiled = step.lower(pool, pool, *[vec] * 7).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the two donated pools are the bulk of the program's footprint
    mem = compiled.memory_analysis()
    pool_bytes = POOL_ROWS * HKV * D * 4
    assert mem.argument_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes


def test_harness_prefill_step_compiles(one_chip):
    step = _prefill_step_fn(MAX_CHUNK, VOCAB, True)
    pool = _spec(one_chip, (POOL_ROWS, HKV, D), jnp.float32)
    vec = _spec(one_chip, (B,), jnp.int32)
    step.lower(pool, pool, vec, vec, vec).compile()


def test_sketch_window_compiles(one_chip):
    rows, n = 8, 128

    def window(state, sizes, weights, lengths, decay, totals):
        return sketch_window_pallas(state, sizes, weights, lengths, decay,
                                    totals)

    _compile(window,
             _spec(one_chip, (TENANT_BUCKETS,), jnp.float32),
             _spec(one_chip, (rows, n), jnp.int32),
             _spec(one_chip, (rows, n), jnp.float32),
             _spec(one_chip, (rows,), jnp.int32),
             _spec(one_chip, (), jnp.float32),
             _spec(one_chip, (rows,), jnp.float32))


def test_waste_eval_fleet_compiles(one_chip):
    def score(chunks, supports, freqs):
        return waste_eval_fleet_pallas(chunks, supports, freqs,
                                       page_size=1 << 14)

    _compile(score,
             _spec(one_chip, (FRONTIER_ROWS, FRONTIER_K), jnp.int32),
             _spec(one_chip, (FRONTIER_ROWS, FRONTIER_S), jnp.int32),
             _spec(one_chip, (FRONTIER_ROWS, FRONTIER_S), jnp.float32))


def test_waste_eval_compiles(one_chip):
    def score(chunks, support, freqs):
        return waste_eval_pallas(chunks, support, freqs, page_size=1 << 14)

    _compile(score,
             _spec(one_chip, (3, FRONTIER_K), jnp.int32),
             _spec(one_chip, (FRONTIER_S,), jnp.int32),
             _spec(one_chip, (FRONTIER_S,), jnp.float32))
