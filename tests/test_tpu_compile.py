"""Compile-only checks of the paged serving kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* v5e topology, refusing tilings and VMEM budgets the
chip would refuse.  Interpret-mode tests (tests/test_kernels.py,
tests/test_prefix_cache.py) cannot see either.  Each case lowers one
paged kernel at a published model width in bf16 and asserts the compiled
program holds the Pallas ``tpu_custom_call`` (not a fallback).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, so every pytest worker
must collect these tests, and only the worker running them loads it.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

# (name, Hq, Hkv, D) at published widths
WIDTHS = [("smollm-135m", 9, 3, 64), ("chatglm-6b", 32, 32, 128)]
SLOTS, BLOCK_TOKENS, NUM_BLOCKS = 16, 16, 512
MAX_BLOCKS = 16                 # (max_len 200 + max_gen 32) / 16, rounded up
SUFFIX = 256                    # the engine's largest suffix bucket at max_len 200
# (id, Hq, Hkv, D, max_blocks, num_blocks) of the paged decode kernel: the
# widths above, and smollm-135m at the benchmark cells' shapes ((max_len
# 512 + max_gen 1024) / 16 = 96-block tables, a 1600-block pool)
DECODE_CASES = [(n, hq, hkv, d, MAX_BLOCKS, NUM_BLOCKS)
                for n, hq, hkv, d in WIDTHS] \
    + [("smollm-135m-bench", 9, 3, 64, 96, 1600)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler / library held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("name,hq,hkv,d,max_blocks,num_blocks", DECODE_CASES,
                         ids=[c[0] for c in DECODE_CASES])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, name, hq, hkv, d,
                                              max_blocks, num_blocks):
    from repro.kernels.decode_attention.kernel import (
        paged_decode_attention_kernel)
    bf = jnp.bfloat16
    pages = _spec((num_blocks, hkv, BLOCK_TOKENS, d), bf, one_chip)
    args = (_spec((SLOTS, hq, d), bf, one_chip), pages, pages,
            _spec((SLOTS, max_blocks), jnp.int32, one_chip),
            _spec((SLOTS,), jnp.int32, one_chip))
    compiled = jax.jit(paged_decode_attention_kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


@pytest.mark.parametrize("name,hq,hkv,d", WIDTHS, ids=[w[0] for w in WIDTHS])
def test_prefix_prefill_kernel_compiles_for_v5e(one_chip, name, hq, hkv, d):
    from repro.kernels.decode_attention.kernel import (
        paged_prefix_prefill_attention_kernel)
    bf = jnp.bfloat16
    b = 4
    pages = _spec((NUM_BLOCKS, hkv, BLOCK_TOKENS, d), bf, one_chip)
    suffix_kv = _spec((b, SUFFIX, hkv, d), bf, one_chip)
    lens = _spec((b,), jnp.int32, one_chip)
    args = (_spec((b, SUFFIX, hq, d), bf, one_chip), suffix_kv, suffix_kv,
            pages, pages, _spec((b, MAX_BLOCKS), jnp.int32, one_chip),
            lens, lens)
    compiled = jax.jit(paged_prefix_prefill_attention_kernel).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
