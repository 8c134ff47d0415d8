"""The main path's Pallas kernels compile for a described TPU v5e.

Interpret mode never checks Mosaic's tiling or VMEM rules, so these
compile the kernels at full qwen3-8b width (d_model 4096, d_ff 12288,
head_dim 128, 8 KV heads x 4 query heads, serving block sizes 16 and 32)
for a ``v5e:2x2`` topology that is described, not attached.  Each case
asserts the compiled program holds a ``tpu_custom_call``.  The topology is
described inside a module-scoped fixture, so only the worker that runs
this file loads the TPU compiler; a host that cannot describe it skips.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels.cim_matmul import ops as mm_ops
from repro.kernels.paged_attention import ops as pa_ops

KVH, G, HD, B, W, NB = 8, 4, 128, 8, 64, 512
CHUNK = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _assert_tpu_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [8, 256])
@pytest.mark.parametrize("k,n", [(4096, 12288), (12288, 4096)])
@pytest.mark.parametrize("in_dtype", [jnp.int8, jnp.float32])
def test_cim_matmul_compiles(sds, m, k, n, in_dtype):
    def fn(a, w, a_s, w_s):
        return mm_ops.cim_matmul(a, w, a_s, w_s, interpret=False)
    _assert_tpu_kernel(fn, sds((m, k), in_dtype), sds((k, n), jnp.int8),
                       sds((), jnp.float32), sds((n,), jnp.float32))


def _pages(sds, bs, int8):
    from repro.core import quant
    shape = (NB, bs, KVH, HD)
    if int8:
        return [quant.QTensor(sds(shape, jnp.int8),
                              sds((*shape[:-1], 1), jnp.bfloat16))
                for _ in range(2)]
    return [sds(shape, jnp.bfloat16) for _ in range(2)]


@pytest.mark.parametrize("bs", [16, 32])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_flash_decode_compiles(sds, bs, int8):
    pk, pv = _pages(sds, bs, int8)

    def fn(q, pk, pv, t, n):
        return pa_ops.paged_attention(q, pk, pv, t, n, backend="pallas")
    _assert_tpu_kernel(fn, sds((B, 1, KVH * G, HD), jnp.bfloat16), pk, pv,
                       sds((B, W), jnp.int32), sds((B,), jnp.int32))


@pytest.mark.parametrize("bs", [16, 32])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_flash_prefill_compiles(sds, bs, int8):
    pk, pv = _pages(sds, bs, int8)

    def fn(q, kn, vn, pk, pv, t, pos, n_tok):
        return pa_ops.paged_prefill(q, kn, vn, pk, pv, t, pos, n_tok,
                                    backend="pallas")
    _assert_tpu_kernel(
        fn, sds((B, CHUNK, KVH * G, HD), jnp.bfloat16),
        sds((B, CHUNK, KVH, HD), jnp.bfloat16),
        sds((B, CHUNK, KVH, HD), jnp.bfloat16), pk, pv,
        sds((B, W), jnp.int32), sds((B,), jnp.int32), sds((B,), jnp.int32))


@pytest.mark.parametrize("w", [64, 128])
@pytest.mark.parametrize("bs", [16, 32])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_grouped_flash_decode_compiles(sds, w, bs, int8):
    """The served decode shape (32 rows, qwen3-8b heads) at the table
    widths the chat traffic dispatches, with the autotuner's pages per
    grid step."""
    pk, pv = _pages(sds, bs, int8)
    dtype = jnp.int8 if int8 else jnp.bfloat16
    assert autotune.choose_paged_decode(
        32, KVH, w, bs, dtype, head_dim=HD, groups=G) \
        == (1, {16: 8, 32: 4}[bs])

    def fn(q, pk, pv, t, n):
        return pa_ops.paged_attention(q, pk, pv, t, n, backend="pallas")
    _assert_tpu_kernel(fn, sds((32, 1, KVH * G, HD), jnp.bfloat16), pk, pv,
                       sds((32, w), jnp.int32), sds((32,), jnp.int32))


def test_grouped_flash_decode_compiles_32_kv_heads(sds):
    """A deepseek-7b-like pool: 32 KV heads, one query head each, int8
    pages of 16 tokens, eight pages a grid step."""
    from repro.core import quant
    shape = (NB, 16, 32, HD)
    pk, pv = [quant.QTensor(sds(shape, jnp.int8),
                            sds((*shape[:-1], 1), jnp.bfloat16))
              for _ in range(2)]
    assert autotune.choose_paged_decode(
        32, 32, 128, 16, jnp.int8, head_dim=HD, groups=1) == (1, 8)

    def fn(q, pk, pv, t, n):
        return pa_ops.paged_attention(q, pk, pv, t, n, backend="pallas")
    _assert_tpu_kernel(fn, sds((32, 1, 32, HD), jnp.bfloat16), pk, pv,
                       sds((32, 128), jnp.int32), sds((32,), jnp.int32))
