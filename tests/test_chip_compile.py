"""Compile the Pallas kernels for a described TPU v5e chip at real widths.

Nothing runs: the TPU compiler that ships with jax compiles for a chip
that is described, not attached, and refuses what the chip's compiler
would refuse (block shapes off the (8, 128) tiling, ops with no Mosaic
lowering, too much VMEM). Interpret-mode tests cannot see any of that.
Each test asserts the kernel reached the program as Mosaic
(``tpu_custom_call``), not as interpreted jnp, under the name its
``pallas_call`` gives it: the op name a device trace shows, which the chip
benchmark's kernel rooflines look up.

Widths: qwen2-0.5b attention (14 q heads over 2 kv heads, head_dim 64,
block_size 16, bf16), mamba2-780m SSD (48 heads of headdim 64, state 128,
chunk 256) and olmoe-1b-7b experts (d_model 2048, d_ff 1024, bf16).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under pytest-xdist every worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import grouped_matmul as gmm
from repro.kernels import paged_attention as pa
from repro.kernels import ssd_scan as ssd

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
# qwen2-0.5b
HKV, G, D, BS = 2, 7, 64, 16
SLOTS, MAX_BLOCKS, N_BLOCKS, CHUNK = 8, 32, 256, 16
# mamba2-780m: d_inner 3072 / headdim 64 = 48 heads
SSM_HEADS, SSM_P, SSM_N, SSM_CHUNK, SSM_SEQ = 48, 64, 128, 256, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, sharding, *shapes):
    structs = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
               for s, dt in shapes]
    return jax.jit(fn).lower(*structs).compile().as_text()


def _custom_call_names(text):
    """The HLO names of the Mosaic custom calls, without ``ROOT`` and the
    numeric suffix."""
    return {line.split(" = ", 1)[0].split()[-1].lstrip("%").rsplit(".", 1)[0]
            for line in text.splitlines() if "tpu_custom_call" in line}


def test_paged_decode_compiles(one_chip):
    fn = functools.partial(pa.paged_attention_bkgd, interpret=False)
    text = _compile_text(fn, one_chip,
                         ((SLOTS, HKV, G, D), BF16),
                         ((N_BLOCKS, HKV, BS, D), BF16),
                         ((N_BLOCKS, HKV, BS, D), BF16),
                         ((SLOTS, MAX_BLOCKS), I32), ((SLOTS,), I32),
                         ((1,), I32))
    assert "tpu_custom_call" in text
    assert _custom_call_names(text) == {"paged_attention"}


def test_paged_prefill_compiles(one_chip):
    fn = functools.partial(pa.paged_prefill_bkgd, interpret=False)
    text = _compile_text(fn, one_chip,
                         ((4, HKV, CHUNK, G, D), BF16),
                         ((N_BLOCKS, HKV, BS, D), BF16),
                         ((N_BLOCKS, HKV, BS, D), BF16),
                         ((4, MAX_BLOCKS), I32), ((4,), I32), ((1,), I32))
    assert "tpu_custom_call" in text
    assert _custom_call_names(text) == {"paged_prefill_attention"}


def test_ssd_scan_compiles(one_chip):
    fn = functools.partial(ssd.ssd_scan_bhsp, chunk=SSM_CHUNK,
                           interpret=False)
    bh = 2 * SSM_HEADS
    text = _compile_text(fn, one_chip,
                         ((bh, SSM_SEQ, SSM_P), F32),
                         ((bh, SSM_SEQ, 1), F32),
                         ((bh, SSM_SEQ, SSM_N), F32),
                         ((bh, SSM_SEQ, SSM_N), F32))
    assert "tpu_custom_call" in text
    assert _custom_call_names(text) == {"ssd_scan"}


def test_flash_attention_compiles(one_chip):
    fn = functools.partial(fa.flash_attention_bhsd, causal=True, bq=128,
                           bk=128, interpret=False)
    b, s = 2, 512
    text = _compile_text(fn, one_chip,
                         ((b * HKV * G, s, D), BF16),
                         ((b * HKV, s, D), BF16),
                         ((b * HKV, s, D), BF16))
    assert "tpu_custom_call" in text
    assert _custom_call_names(text) == {"flash_attention"}


def test_grouped_matmul_compiles(one_chip):
    fn = functools.partial(gmm.grouped_matmul, interpret=False)
    experts, capacity, d_model, d_ff = 8, 256, 2048, 1024
    text = _compile_text(fn, one_chip,
                         ((experts, capacity, d_model), BF16),
                         ((experts, d_model, d_ff), BF16),
                         ((experts,), I32))
    assert "tpu_custom_call" in text
    assert _custom_call_names(text) == {"grouped_matmul"}
