"""Kernel block autotuner: determinism, pow2 bucketing, JSON round-trip,
and the ops-layer aligned fast path / bucketed padding."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels.cim_matmul import cim_matmul, cim_matmul_ref


@pytest.fixture(autouse=True)
def _fresh_table():
    autotune.clear()
    yield
    autotune.clear()


def test_choose_blocks_deterministic():
    a = autotune.choose_blocks(7, 512, 256)
    b = autotune.choose_blocks(7, 512, 256)
    assert a == b
    # pure heuristic is stable across table clears too
    autotune.clear()
    assert autotune.choose_blocks(7, 512, 256) == a


def test_m_bucketing_collapses_decode_batches():
    """Batches 1..8 share one bucket, 9..16 the next: O(log B) kernels."""
    keys = {autotune.m_bucket(m) for m in range(1, 9)}
    assert keys == {8}
    assert autotune.m_bucket(9) == autotune.m_bucket(16) == 16
    assert autotune.m_bucket(17) == 32
    # and the block choice is shared within a bucket
    assert autotune.choose_blocks(3, 256, 128) == \
        autotune.choose_blocks(8, 256, 128)


def test_blocks_are_mxu_aligned_or_pad_free():
    for (m, k, n) in [(1, 1152, 128), (32, 512, 256), (256, 4096, 1024)]:
        bm, bn, bk = autotune.choose_blocks(m, k, n)
        assert bm <= 256 and bm == autotune.m_bucket(min(m, 256)) or bm == 256
        assert bn == n or bn % 128 == 0
        assert bk == k or bk % 128 == 0


def test_float_dtype_halves_k_block():
    _, _, bk_i8 = autotune.choose_blocks(32, 1024, 256, jnp.int8)
    _, _, bk_f32 = autotune.choose_blocks(32, 1024, 256, jnp.float32)
    assert bk_f32 <= 256 <= bk_i8


def test_record_and_json_round_trip(tmp_path):
    autotune.record(16, 512, 256, jnp.int8, (16, 128, 256))
    assert autotune.choose_blocks(16, 512, 256) == (16, 128, 256)
    path = tmp_path / "table.json"
    autotune.dump(str(path))
    autotune.clear()
    assert autotune.choose_blocks(16, 512, 256) != (16, 128, 256) or True
    autotune.clear()
    n = autotune.load(str(path))
    assert n == 1
    assert autotune.choose_blocks(16, 512, 256) == (16, 128, 256)


def test_measure_smoke_records_choice():
    best, timings = autotune.measure(8, 128, 64, iters=1)
    assert best in timings
    assert autotune.choose_blocks(8, 128, 64) == best


def _inputs(m, k, n):
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    a = jax.random.randint(k1, (m, k), -128, 128, jnp.int32).astype(jnp.int8)
    w = jax.random.randint(k2, (k, n), -128, 128, jnp.int32).astype(jnp.int8)
    ws = jax.random.uniform(k3, (n,), minval=0.01, maxval=0.2)
    return a, w, jnp.float32(0.07), ws


def test_ops_autotuned_default_blocks_correct():
    """cim_matmul with no block args routes through the autotuner."""
    for (m, k, n) in [(1, 96, 64), (5, 128, 96), (33, 512, 256)]:
        a, w, a_s, ws = _inputs(m, k, n)
        ref = cim_matmul_ref(a, w, a_s, ws, jnp.zeros((n,)), jnp.float32(1.0))
        got = cim_matmul(a, w, a_s, ws)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-3)


def test_ops_pow2_bucket_shares_blocks_and_correctness():
    """Decode batches in one pow2 bucket all resolve to the same blocks
    (and so pad to one shared kernel shape), and stay correct."""
    from repro.kernels.cim_matmul import ops
    a, w, a_s, ws = _inputs(8, 128, 64)
    blocks = set()
    for m in (1, 3, 5, 8):
        am = _inputs(m, 128, 64)[0]
        got = cim_matmul(am, w, a_s, ws)
        ref = cim_matmul_ref(am, w, a_s, ws, jnp.zeros((64,)),
                             jnp.float32(1.0))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-3)
        blocks.add(autotune.choose_blocks(m, 128, 64))
    assert len(blocks) == 1  # one bucket -> one block config -> one kernel


def test_measure_overrides_already_traced_shape():
    """Blocks resolve outside the jit boundary: a measured/loaded table
    entry takes effect even after the shape has already run."""
    a, w, a_s, ws = _inputs(8, 128, 64)
    cim_matmul(a, w, a_s, ws)                        # traced w/ heuristic
    autotune.record(8, 128, 64, jnp.int8, (8, 32, 64))
    got = cim_matmul(a, w, a_s, ws)                  # re-resolves -> new jit
    ref = cim_matmul_ref(a, w, a_s, ws, jnp.zeros((64,)), jnp.float32(1.0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-3)
    assert autotune.choose_blocks(8, 128, 64) == (8, 32, 64)


def test_ops_aligned_shapes_skip_pad_and_slice():
    """Block-aligned shapes produce identical results through the no-pad
    fast path (vs explicitly pinned identical blocks)."""
    a, w, a_s, ws = _inputs(32, 256, 128)
    got = cim_matmul(a, w, a_s, ws, bm=32, bn=128, bk=256)
    ref = cim_matmul_ref(a, w, a_s, ws, jnp.zeros((128,)), jnp.float32(1.0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("kvh,bs,dtype,want", [
    (8, 16, jnp.int8, 8),          # qwen3-8b int8 pool
    (8, 32, jnp.int8, 4),
    (32, 16, jnp.int8, 8),         # deepseek-7b: 32 KV heads
    (8, 16, jnp.bfloat16, 8),
    (8, 32, jnp.bfloat16, 4),
    (32, 16, jnp.bfloat16, 4),     # 128 KiB pages: the byte cap decides
])
@pytest.mark.parametrize("width", [64, 128])
def test_paged_decode_choice_at_served_shapes(kvh, bs, dtype, want, width):
    """(kv_splits, pages_per_step) for the served decode shapes: one
    split, and 128 tokens a step unless one operand's pages would pass
    512 KiB."""
    assert autotune.choose_paged_decode(32, kvh, width, bs, dtype,
                                        head_dim=128, groups=4) == (1, want)


def test_pages_per_step_never_exceeds_the_split_width():
    for width in range(1, 70):
        for splits in (1, 2, 3, 8):
            for kvh, bs, hd in ((1, 4, 16), (8, 16, 128), (32, 32, 128)):
                p = autotune.heuristic_pages_per_step(width, splits, bs, kvh,
                                                      hd, jnp.int8)
                assert 1 <= p <= -(-width // min(splits, width))
                assert p & (p - 1) == 0
        assert autotune.choose_paged_decode(
            4, 2, width, 4, jnp.int8, head_dim=16)[1] <= width


def test_decode_grouping_follows_the_split_count():
    """``ops.decode_grouping`` (what the kernel runs and the engine's span
    reports) reads a layer-stacked int8 pool's page shape, and its pages
    per step follow whatever split count runs: a measured one, or the
    caller's, never a stored pages-per-step."""
    from repro.core import quant
    from repro.kernels.paged_attention import ops as pops
    codes = jnp.zeros((2, 4, 16, 8, 128), jnp.int8)      # [L,NB,BS,KVH,D]
    pool = quant.QTensor(codes, jnp.ones((*codes.shape[:-1], 1),
                                         jnp.bfloat16))
    assert pops.decode_grouping(32, pool, 64, 4) == (1, 8)
    assert pops.decode_grouping(32, pool, 64, 4, kv_splits=16) == (16, 4)
    autotune.record_paged(32, 8, 64, 16, jnp.int8, 16, head_dim=128,
                          groups=4)
    assert pops.decode_grouping(32, pool, 64, 4) == (16, 4)
    entry = json.loads(autotune.dump())["paged_entries"][0]
    assert entry["kv_splits"] == 16 and "pages_per_step" not in entry
