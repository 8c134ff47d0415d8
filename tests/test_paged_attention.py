"""Fused paged-attention kernel: parity vs the gather reference (fp and
int8), ragged lengths, null-block masking, GQA, split-KV equivalence,
backend agreement (Pallas interpreter vs jnp emulation), page groups per
grid step, autotuned splits and groups, and the DeploymentPlan wiring."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as backend_lib
from repro.core import quant
from repro.kernels import autotune
from repro.kernels.paged_attention import kernel as paged_kernel
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention import ref as paged_ref
from repro.models import attention as A

B, S, H, KVH, D, BS = 2, 32, 4, 2, 16, 4


def _pool(seed=0, *, int8=False, n_extra_blocks=0, garbage=False):
    """Dense K/V scattered into pages + tables (one page chain per row).

    With ``garbage`` the null block and every unreferenced block are filled
    with huge values — anything leaking past the table/length masks shows
    up immediately."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    k = jax.random.normal(ks[1], (B, S, KVH, D))
    v = jax.random.normal(ks[2], (B, S, KVH, D))
    nbr = S // BS
    nb = 1 + B * nbr + n_extra_blocks
    shape = (nb, BS, KVH, D)
    if int8:
        fill = 111 if garbage else 0
        pk = quant.QTensor(jnp.full(shape, fill, jnp.int8),
                           jnp.full((*shape[:-1], 1),
                                    1e4 if garbage else 0, jnp.bfloat16))
        pv = quant.QTensor(jnp.full(shape, fill, jnp.int8),
                           jnp.full((*shape[:-1], 1),
                                    1e4 if garbage else 0, jnp.bfloat16))
    else:
        fill = 1e8 if garbage else 0.0
        pk = jnp.full(shape, fill)
        pv = jnp.full(shape, fill)
    tables = np.zeros((B, nbr), np.int32)
    nxt = 1
    for row in range(B):
        for j in range(nbr):
            tables[row, j] = nxt
            sl = slice(j * BS, (j + 1) * BS)
            if int8:
                kq, ksc = A.quantize_kv(k[row:row + 1, sl])
                vq, vsc = A.quantize_kv(v[row:row + 1, sl])
                pk = pk.at_set(nxt, quant.QTensor(kq[0], ksc[0][..., None]))
                pv = pv.at_set(nxt, quant.QTensor(vq[0], vsc[0][..., None]))
            else:
                pk = pk.at[nxt].set(k[row, sl])
                pv = pv.at[nxt].set(v[row, sl])
            nxt += 1
    return q, pk, pv, jnp.asarray(tables)


# ---------------------------------------------------------------------------
# Parity vs the gather reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["emulate", "interpret"])
@pytest.mark.parametrize("lens", [(13, 32), (1, 7), (32, 32)])
def test_fused_matches_reference_fp(backend, lens):
    """fp pools: fused == gather reference to fp rounding, ragged n_valid,
    GQA head groups (H=4 query heads over KVH=2)."""
    q, pk, pv, tables = _pool(0)
    nv = jnp.asarray(lens, jnp.int32)
    want = A.attend_decode_paged(q, pk, pv, tables, nv)
    got = paged_ops.paged_attention(q, pk, pv, tables, nv, kv_splits=2,
                                    backend=backend)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["emulate", "interpret"])
def test_fused_int8_tight_vs_dequant_loose_vs_integer(backend):
    """int8 pools: the kernel streams int8 pages and dequantizes
    in-registers but keeps q and the probabilities in f32, so it matches
    fp attention over the dequantized pages tightly while the fully-
    integer reference (which also quantizes q and requantizes p) agrees
    only to its own quantization error."""
    q, pk, pv, tables = _pool(1, int8=True)
    nv = jnp.asarray([13, 29], jnp.int32)
    got = paged_ops.paged_attention(q, pk, pv, tables, nv, kv_splits=2,
                                    backend=backend)
    tight = paged_ref.dequant_attention_ref(q, pk, pv, tables, nv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(tight),
                               rtol=1e-5, atol=1e-5)
    integer = paged_ref.paged_attention_ref(q, pk, pv, tables, nv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(integer),
                               rtol=0.1, atol=0.1)


def test_kernel_interpret_agrees_with_emulation():
    """The Pallas kernel (interpret) and the vectorized jnp emulation are
    the same math — fp-rounding-level agreement on fp AND int8 pools."""
    for int8 in (False, True):
        q, pk, pv, tables = _pool(2, int8=int8)
        nv = jnp.asarray([9, 27], jnp.int32)
        a = paged_ops.paged_attention(q, pk, pv, tables, nv, kv_splits=2,
                                      backend="interpret")
        b = paged_ops.paged_attention(q, pk, pv, tables, nv, kv_splits=2,
                                      backend="emulate")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend", ["emulate", "interpret"])
def test_split_kv_equivalence(backend):
    """Split-KV partial softmax + logsumexp merge == single split, for
    every split count up to one page per program (incl. non-divisors)."""
    q, pk, pv, tables = _pool(3)
    nv = jnp.asarray([21, 32], jnp.int32)
    base = paged_ops.paged_attention(q, pk, pv, tables, nv, kv_splits=1,
                                     backend=backend)
    for splits in (2, 3, tables.shape[1]):
        got = paged_ops.paged_attention(q, pk, pv, tables, nv,
                                        kv_splits=splits, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("int8", [False, True])
def test_null_block_and_dead_table_masking(int8):
    """Garbage in the null block and in unreferenced pool blocks never
    reaches the output: table padding entries and positions >= n_valid are
    fully masked (the index map clamps to live pages, the kernel masks the
    tail slots)."""
    q, pk, pv, tables = _pool(4, int8=int8)
    q2, gk, gv, _ = _pool(4, int8=int8, n_extra_blocks=3, garbage=True)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))

    def patch(garbage, clean):
        # garbage pool with the SAME live pages as the clean pool
        if int8:
            nb = clean.q.shape[0]
            return quant.QTensor(
                garbage.q.at[1:nb].set(clean.q[1:]),
                garbage.scale.at[1:nb].set(clean.scale[1:]))
        return garbage.at[1:clean.shape[0]].set(clean[1:])

    gk, gv = patch(gk, pk), patch(gv, pv)
    nv = jnp.asarray([10, 30], jnp.int32)
    for backend in ("emulate", "interpret"):
        clean = paged_ops.paged_attention(q, pk, pv, tables, nv,
                                          kv_splits=2, backend=backend)
        dirty = paged_ops.paged_attention(q, gk, gv, tables, nv,
                                          kv_splits=2, backend=backend)
        np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


def test_empty_request_row_is_finite_zeros():
    """n_valid == 0 rows return exact zeros (the gather reference returns
    a masked-softmax-of-nothing garbage value there; serve discards both,
    but the fused path must never emit NaN into the batch)."""
    q, pk, pv, tables = _pool(5)
    nv = jnp.asarray([0, 32], jnp.int32)
    for backend in ("emulate", "interpret"):
        got = paged_ops.paged_attention(q, pk, pv, tables, nv, kv_splits=2,
                                        backend=backend)
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.zeros_like(np.asarray(got[0])))
        want = A.attend_decode_paged(q, pk, pv, tables, nv)
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   rtol=1e-5, atol=1e-5)


def test_n_valid_beyond_table_clamps_identically():
    """n_valid past the handed-in table's capacity (W * BS) clamps to it
    in EVERY backend: split padding and out-of-table positions never
    attend, so emulate and the kernel agree outside the serve loop's
    n_valid <= W*BS contract too."""
    q, pk, pv, tables = _pool(8)
    bt = tables[:, :3]                            # capacity 12 positions
    over = jnp.asarray([13, 99], jnp.int32)       # > W * BS
    capped = jnp.asarray([12, 12], jnp.int32)
    for backend in ("emulate", "interpret"):
        a = paged_ops.paged_attention(q, pk, pv, bt, over, kv_splits=2,
                                      backend=backend)
        b = paged_ops.paged_attention(q, pk, pv, bt, capped, kv_splits=2,
                                      backend=backend)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    a = paged_ops.paged_attention(q, pk, pv, bt, over, kv_splits=2,
                                  backend="emulate")
    b = paged_ops.paged_attention(q, pk, pv, bt, over, kv_splits=2,
                                  backend="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


def test_truncated_table_width_matches_full(monkeypatch):
    """The serve loop dispatches live-width table prefixes; results match
    the full-width call whenever the truncation covers n_valid."""
    q, pk, pv, tables = _pool(6)
    nv = jnp.asarray([7, 8], jnp.int32)          # 2 live pages per row
    full = A.attend_decode_paged(q, pk, pv, tables, nv)
    for backend in ("emulate", "interpret"):
        got = paged_ops.paged_attention(q, pk, pv, tables[:, :2], nv,
                                        kv_splits=1, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Page groups: the kernel's pages_per_step against the emulation
# ---------------------------------------------------------------------------

def _scattered_pool(seed, *, bs, width, lens, int8, kvh=2, g=2, d=16):
    """Pages of every row scattered over a pool whose null block 0 and
    spare blocks hold garbage; dead table entries point at block 0.
    Returns ``(q [B,KVH,G,D], k, k_scale, v, v_scale, tables, n_valid)``
    in the kernel's operand form."""
    rng = np.random.default_rng(seed)
    b = len(lens)
    live = [min(-(-n // bs), width) for n in lens]
    nb = 1 + sum(live) + 3
    perm = rng.permutation(np.arange(1, nb))
    tables = np.zeros((b, width), np.int32)
    nxt = 0
    for r, n in enumerate(live):
        tables[r, :n] = perm[nxt:nxt + n]
        nxt += n
    shape = (nb, bs, kvh, d)
    q = jnp.asarray(rng.standard_normal((b, kvh, g, d)), jnp.float32)

    def pool():
        x = rng.standard_normal(shape).astype(np.float32)
        if not int8:
            x[0], x[perm[nxt:]] = 1e8, 1e8
            return jnp.asarray(x), None
        codes, scale = A.quantize_kv(jnp.asarray(x))
        codes = codes.at[0].set(111)
        scale = scale.at[0].set(1e4).at[perm[nxt:]].set(1e4)
        return codes, scale

    (k, ks), (v, vs) = pool(), pool()
    return q, k, ks, v, vs, jnp.asarray(tables), jnp.asarray(lens, jnp.int32)


def _grouped_vs_emulated(ops, kv_splits, pages_per_step):
    q, k, ks, v, vs, tables, nv = ops
    want = paged_ops.flash_decode_jnp(q, k, ks, v, vs, tables, nv,
                                      kv_splits=kv_splits)
    acc, m, l = paged_kernel.paged_attention_kernel(
        q, k, v, ks, vs, tables, nv, kv_splits=kv_splits,
        pages_per_step=pages_per_step, interpret=True)
    got = paged_ops.merge_splits(acc, m, l)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("int8", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("bs", [16, 32])
@pytest.mark.parametrize("pages_per_step", [1, 2, 4, 8])
def test_grouped_kernel_matches_emulation(pages_per_step, bs, int8):
    """Rows of length 0, 1, exactly one group, one past it and beyond the
    table (clamped to it), over a width the group does not divide, with
    dead entries on the garbage null block: the grouped kernel agrees
    with the vectorized emulation."""
    p = pages_per_step
    width = 2 * p + 1
    lens = [0, 1, p * bs, p * bs + 1, width * bs + 7]
    ops = _scattered_pool(p * 10 + bs + int8, bs=bs, width=width, lens=lens,
                          int8=int8)
    _grouped_vs_emulated(ops, 1, p)


@pytest.mark.parametrize("kv_splits,pages_per_step",
                         [(2, 4), (4, 2), (2, 8)])
def test_grouped_kernel_split_kv(kv_splits, pages_per_step):
    """kv_splits > 1 with several pages a step: a split's last group
    reaching into the next split (6 and 3 columns a split), and a group
    capped at a split's width, merge to the emulation."""
    width = 11
    lens = [0, 7, 16 * 5, 16 * 6 + 3, 16 * width]
    ops = _scattered_pool(kv_splits + pages_per_step, bs=16, width=width,
                          lens=lens, int8=True)
    _grouped_vs_emulated(ops, kv_splits, pages_per_step)


def test_grouped_kernel_head_dim_120():
    """A head_dim that is not a multiple of 128 runs through interpret
    mode with the autotuner's grouping."""
    ops = _scattered_pool(5, bs=16, width=8, lens=[33, 128, 90], int8=True,
                          kvh=2, g=4, d=120)
    p = autotune.heuristic_pages_per_step(8, 1, 16, 2, 120, ops[1].dtype)
    assert p == 8
    _grouped_vs_emulated(ops, 1, p)


# ---------------------------------------------------------------------------
# gather_pages tight bound (the kept reference stops scaling with the pool)
# ---------------------------------------------------------------------------

def test_gather_pages_tight_bound():
    q, pk, pv, tables = _pool(7)
    nv = np.asarray([5, 9], np.int32)             # max 9 -> 3 pages
    tight = A.gather_pages(pk, tables, nv)
    assert tight.shape[1] == 3 * BS               # ceil(9 / 4) blocks
    full = A.gather_pages(pk, tables)
    np.testing.assert_array_equal(np.asarray(tight),
                                  np.asarray(full[:, :3 * BS]))
    # the reference path with n_valid is unchanged numerically
    a = A.attend_decode_paged(q, pk, pv, tables, jnp.asarray(nv))
    b = A.attend_decode(
        q, full, A.gather_pages(pv, tables),
        jnp.arange(full.shape[1])[None] < jnp.asarray(nv)[:, None])
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)
    # traced n_valid (inside jit) falls back to the full width — no error
    jitted = jax.jit(lambda nv: A.gather_pages(pk, tables, nv))
    np.testing.assert_array_equal(np.asarray(jitted(jnp.asarray(nv))),
                                  np.asarray(full))


# ---------------------------------------------------------------------------
# Autotune: split count / pages-per-program
# ---------------------------------------------------------------------------

def test_autotune_paged_heuristic_and_roundtrip(tmp_path):
    autotune.clear()
    try:
        # deterministic + memoized
        s1 = autotune.choose_paged_decode(2, 2, 8, 4, jnp.int8, head_dim=16)
        assert s1 == autotune.choose_paged_decode(2, 2, 8, 4, jnp.int8,
                                                  head_dim=16)
        # one chain per split: the heuristic never splits
        assert autotune.choose_paged_decode(8, 8, 16, 4,
                                            head_dim=16)[0] == 1
        assert autotune.choose_paged_decode(1, 1, 4, 4, head_dim=16)[0] == 1
        # measured entries override and survive a dump/load round trip;
        # the key is shape-complete, so another head_dim never collides
        autotune.record_paged(2, 2, 8, 4, jnp.int8, 4, head_dim=16)
        assert autotune.choose_paged_decode(2, 2, 8, 4, jnp.int8,
                                            head_dim=16)[0] == 4
        assert autotune.choose_paged_decode(2, 2, 8, 4, jnp.int8,
                                            head_dim=128) == s1
        path = tmp_path / "tune.json"
        autotune.dump(str(path))
        autotune.clear()
        assert autotune.load(str(path)) >= 1
        assert autotune.choose_paged_decode(2, 2, 8, 4, jnp.int8,
                                            head_dim=16)[0] == 4
    finally:
        autotune.clear()


def test_autotune_measure_paged_smoke():
    autotune.clear()
    try:
        best, timings = autotune.measure_paged(
            2, 2, 4, 4, jnp.float32, head_dim=8, groups=2,
            candidates=(1, 2), iters=1, backend="emulate")
        assert best in timings and set(timings) == {1, 2}
        assert autotune.choose_paged_decode(
            2, 2, 4, 4, jnp.float32, head_dim=8, groups=2)[0] == best
    finally:
        autotune.clear()


# ---------------------------------------------------------------------------
# Plan wiring: attention() paged branch behind DeploymentPlan.paged_attn
# ---------------------------------------------------------------------------

def test_plan_paged_attn_json_roundtrip():
    plan = backend_lib.DeploymentPlan(default="w8a8", paged_attn=True)
    assert backend_lib.paged_attn_enabled(plan)
    assert not backend_lib.paged_attn_enabled(
        backend_lib.DeploymentPlan(default="w8a8"))
    assert not backend_lib.paged_attn_enabled("w8a8")
    back = backend_lib.DeploymentPlan.from_json(plan.to_json())
    assert back == plan and back.paged_attn


def test_attention_layer_paged_branch_fused_vs_reference():
    """Full attention() layer call on a paged cache: the fused plan routes
    through the kernel and matches the reference plan's output."""
    from repro import configs as cfg_lib
    cfg = cfg_lib.reduced_config("qwen3-8b", n_layers=1)
    hd = cfg.resolved_head_dim
    key = jax.random.PRNGKey(0)
    p = A.init_attention(key, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd,
                         cfg.qk_norm, jnp.float32)
    x = jax.random.normal(key, (2, 1, cfg.d_model), jnp.float32)
    nb, bs, nbr = 9, 4, 4
    pages_shape = (nb, bs, cfg.n_kv_heads, hd)
    kv = {
        "k": jax.random.normal(key, pages_shape, jnp.float32),
        "v": jax.random.normal(key, pages_shape, jnp.float32),
        "block_tables": jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]],
                                    jnp.int32),
        "lens": jnp.asarray([6, 11], jnp.int32),
        "write_mask": jnp.asarray([True, True]),
    }
    ref_plan = backend_lib.DeploymentPlan(default="exact")
    fus_plan = dataclasses.replace(ref_plan, paged_attn=True)
    y_ref, c_ref = A.attention(p, x, cfg, kv_cache=dict(kv), mode=ref_plan)
    y_fus, c_fus = A.attention(p, x, cfg, kv_cache=dict(kv), mode=fus_plan)
    np.testing.assert_allclose(np.asarray(y_fus), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    # cache writes are identical (the kernel only changes the read path)
    np.testing.assert_array_equal(np.asarray(c_fus["k"]),
                                  np.asarray(c_ref["k"]))
    np.testing.assert_array_equal(np.asarray(c_fus["v"]),
                                  np.asarray(c_ref["v"]))


def test_kernel_scan_times_each_grouping(tmp_path):
    """``benchmarks/paged_attention.py --kernel-scan`` (the kernel-alone
    timing) runs end to end through the interpreter at a toy size: one row
    per width and pages-per-step, 0 resolved to the autotuner's choice,
    live pages drawn from a traffic file and bounded by the table."""
    import json
    import os
    import pathlib
    import subprocess
    import sys
    repo = pathlib.Path(__file__).resolve().parents[1]
    out = tmp_path / "scan.json"
    env = dict(os.environ, PYTHONPATH=str(repo / "src"), JAX_PLATFORMS="cpu")
    subprocess.run(
        [sys.executable, str(repo / "benchmarks" / "paged_attention.py"),
         "--kernel-scan", "--batch", "2", "--kv-heads", "2", "--groups", "2",
         "--head-dim", "128", "--kv-blocks", "24", "--widths", "2", "8",
         "--layers", "1", "--iters", "1", "--pages-per-step", "1", "0",
         "--traffic", str(repo / "bench" / "traffic" / "chat-poisson.json"),
         "--out", str(out)], check=True, env=env, cwd=tmp_path,
        capture_output=True)
    rows = json.loads(out.read_text())["rows"]
    assert [(r["width"], r["pages_per_step"]) for r in rows] == [
        (2, 1), (2, 2), (8, 1), (8, 8)]
    for r in rows:
        assert r["call_us"] > 0
        assert 2 <= r["live_pages"] <= 2 * r["width"]
