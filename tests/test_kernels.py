"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode.

The M2L oracle is the pre-folding 40-offset masked formulation
(``expansions.m2l_masked40``), so these tests also pin the parity-folded
math — jnp and Pallas — against an independent implementation.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import expansions as ex
from repro.kernels import ref
from repro.kernels.flash_attn import flash_attention
from repro.kernels.m2l import m2l_pallas, m2l_pallas_slab
from repro.kernels.p2p import p2p_pallas, p2p_pallas_slab
from repro.core.fmm import fmm_velocity
from repro.core.quadtree import build_tree


def _rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


# ---------------------------------------------------------------------------
# P2P kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ny,nx,s", [(4, 4, 3), (8, 8, 5), (8, 16, 1), (6, 6, 8)])
@pytest.mark.parametrize("sigma", [None, 0.05])
def test_p2p_kernel_sweep(ny, nx, s, sigma):
    rng = np.random.default_rng(ny * 100 + nx + s)
    z = (rng.uniform(size=(ny, nx, s)) + 1j * rng.uniform(size=(ny, nx, s)))
    q = (rng.normal(size=(ny, nx, s)) + 1j * rng.normal(size=(ny, nx, s)))
    mask = rng.uniform(size=(ny, nx, s)) > 0.3
    z, q = jnp.asarray(z, jnp.complex64), jnp.asarray(q, jnp.complex64)
    mask = jnp.asarray(mask)
    out = p2p_pallas(z, q, mask, sigma=sigma, block=(4, 4))
    expect = ref.p2p_ref(z, q, mask, sigma=sigma)
    expect = jnp.where(mask, expect, 0)  # kernel computes everywhere; compare masked
    out = jnp.where(mask, out, 0)
    assert _rel(out, expect) < 1e-5


def test_p2p_kernel_block_size_invariance():
    """(BY, BX) is a pure perf knob — outputs must agree across shapes."""
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.uniform(size=(8, 8, 4)) + 1j * rng.uniform(size=(8, 8, 4)),
                    jnp.complex64)
    q = jnp.asarray(rng.normal(size=(8, 8, 4)) + 0j, jnp.complex64)
    mask = jnp.ones((8, 8, 4), bool)
    outs = [np.asarray(p2p_pallas(z, q, mask, sigma=0.1, block=b))
            for b in ((2, 2), (4, 8), (8, 8), (16, 16))]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-6, atol=1e-6)


def test_p2p_slab_matches_grid():
    """The slab entry point (ghosts attached by caller) == grid wrapper."""
    rng = np.random.default_rng(5)
    z = jnp.asarray(rng.uniform(size=(8, 8, 3)) + 1j * rng.uniform(size=(8, 8, 3)),
                    jnp.complex64)
    q = jnp.asarray(rng.normal(size=(8, 8, 3)) + 0j, jnp.complex64)
    mask = jnp.asarray(rng.uniform(size=(8, 8, 3)) > 0.2)
    full = np.asarray(p2p_pallas(z, q, mask, sigma=0.05, block=(4, 4)))
    # slab = grid rows 2..5; ghost rows 1 and 6 are true neighbor rows
    cpad = ((0, 0), (1, 1), (0, 0))
    out = np.asarray(p2p_pallas_slab(jnp.pad(z[1:7], cpad),
                                     jnp.pad(q[1:7], cpad),
                                     jnp.pad(mask[1:7], cpad),
                                     sigma=0.05, block=(4, 4)))
    m = np.asarray(mask[2:6])
    np.testing.assert_allclose(np.where(m, out, 0), np.where(m, full[2:6], 0),
                               rtol=2e-5, atol=1e-5)


def _slab_inputs(rng, rows, cols, s, st, q_real=False):
    sh = (rows + 2, cols + 2, s)
    z = jnp.asarray(rng.uniform(size=sh) + 1j * rng.uniform(size=sh),
                    jnp.complex64)
    q = rng.normal(size=sh) + (0 if q_real else 1j * rng.normal(size=sh))
    mask = jnp.asarray(rng.uniform(size=sh) > 0.3)
    z_tgt = None if st is None else jnp.asarray(
        rng.uniform(size=(rows, cols, st))
        + 1j * rng.uniform(size=(rows, cols, st)), jnp.complex64)
    return z, jnp.asarray(q, jnp.complex64), mask, z_tgt


@pytest.mark.parametrize("rows,cols,s,st,eq,lane_pad", [
    (6, 10, 1, None, None, False),       # one slot
    (9, 12, 4, None, None, True),        # 12 columns pad to one 128-lane block
    (4, 6, 50, None, None, True),        # 50 slots: several target chunks
    (3, 256, 4, None, None, True),       # two 128-lane column blocks
    (5, 200, 5, None, None, True),       # 200 columns pad to 256
    (20, 6, 3, None, None, True),        # rows > cols: rows go on the lanes
    (8, 8, 5, 3, None, False),           # passive targets, st < s
    (7, 9, 4, 9, None, True),            # passive targets, st > s
    (6, 9, 5, None, "laplace", False),   # two output channels
])
@pytest.mark.parametrize("sigma", [None, 0.05])
def test_p2p_kernel_matches_slab_reference(rows, cols, s, st, eq, lane_pad,
                                           sigma):
    """Boxes on the lanes, slots leading: the kernel equals the jnp slab
    reference for any slot count, column extent and target mode."""
    from repro.core import equations as eqs
    from repro.core.fmm import p2p_slab_reference
    rng = np.random.default_rng(rows * 1000 + cols * 10 + s)
    z, q, mask, z_tgt = _slab_inputs(rng, rows, cols, s, st,
                                     eqs.get_equation(eq).q_is_real)
    out = p2p_pallas_slab(z, q, mask, sigma=sigma, lane_pad=lane_pad,
                          z_tgt=z_tgt, eq=eq)
    want = p2p_slab_reference(z, q, mask, sigma, z_tgt=z_tgt, eq=eq)
    assert out.shape == want.shape
    if z_tgt is None:                      # masked-off targets: don't care
        m = np.asarray(mask[1:-1, 1:-1])
        m = m[..., None] if out.ndim == 4 else m
        out, want = np.where(m, out, 0), np.where(m, want, 0)
    assert _rel(out, want) < 1e-5


def test_p2p_source_slot_chunks_accumulate(monkeypatch):
    """Slots beyond one VMEM window split into source chunks whose partial
    sums accumulate in the resident output block."""
    from repro.core.fmm import p2p_slab_reference
    from repro.kernels import p2p as kp
    rng = np.random.default_rng(31)
    z, q, mask, _ = _slab_inputs(rng, 5, 11, 13, None)
    want = np.asarray(p2p_pallas_slab(z, q, mask, sigma=0.05))
    # five fields, three column shifts, one 10 x 8 f32 window a slot: four
    # slots a chunk
    monkeypatch.setattr(kp, "WINDOW_BYTES", 5 * 3 * 4 * 10 * 8 * 4)
    jax.clear_caches()
    try:
        out = np.asarray(p2p_pallas_slab(z, q, mask, sigma=0.05))
    finally:
        jax.clear_caches()
    ref_out = np.asarray(p2p_slab_reference(z, q, mask, 0.05))
    m = np.asarray(mask[1:-1, 1:-1])
    np.testing.assert_allclose(np.where(m, out, 0), np.where(m, want, 0),
                               rtol=1e-5, atol=1e-5)
    assert _rel(np.where(m, out, 0), np.where(m, ref_out, 0)) < 1e-5


# ---------------------------------------------------------------------------
# M2L kernel (parity-folded, halo-resident)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("level,p", [(2, 4), (3, 8), (4, 17), (5, 12)])
def test_m2l_kernel_sweep(level, p):
    rng = np.random.default_rng(level * 10 + p)
    n = 1 << level
    me = jnp.asarray(rng.normal(size=(n, n, p)) + 1j * rng.normal(size=(n, n, p)),
                     jnp.complex64)
    out = m2l_pallas(me, level, p, block=(4, 4))
    expect = ref.m2l_ref(me, level, p)
    assert _rel(out, expect) < 1e-5


def test_m2l_kernel_block_size_sweep_equivalence():
    """(BY, BX) sweep: every block shape produces the same LE grid."""
    rng = np.random.default_rng(2)
    level, p = 4, 17
    n = 1 << level
    me = jnp.asarray(rng.normal(size=(n, n, p)) + 1j * rng.normal(size=(n, n, p)),
                     jnp.complex64)
    outs = [np.asarray(m2l_pallas(me, level, p, block=b))
            for b in ((1, 1), (2, 4), (4, 2), (8, 8), (16, 16))]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("row0,rows,halo", [(0, 4, 2), (4, 8, 2), (1, 5, 3),
                                            (3, 7, 3), (5, 2, 3)])
def test_m2l_slab_rectangular_row0_parity(row0, rows, halo):
    """Rectangular slabs, including odd ``row0`` parity origins, match the
    corresponding rows of the full-grid masked oracle — jnp and Pallas."""
    rng = np.random.default_rng(row0 * 7 + rows)
    level, p = 4, 7
    n = 1 << level
    me = jnp.asarray(rng.normal(size=(n, n, p)) + 1j * rng.normal(size=(n, n, p)),
                     jnp.complex64)
    full = np.asarray(ex.m2l_masked40(me, level, p))
    pad = jnp.pad(me, ((3, 3), (0, 0), (0, 0)))
    me_halo = pad[3 + row0 - halo:3 + row0 + rows + halo]
    want = full[row0:row0 + rows]
    got_jnp = np.asarray(ex.m2l_folded(me_halo, level, p, row0=row0, halo=halo))
    got_pls = np.asarray(m2l_pallas_slab(me_halo, level, p, row0=row0,
                                         halo=halo, block=(4, 4)))
    assert _rel(got_jnp, want) < 1e-5
    assert _rel(got_pls, want) < 1e-5


def test_m2l_folded_reference_matches_masked40_p17():
    """The folded jnp hot path == 40-offset masked oracle at p=17, 1e-5."""
    rng = np.random.default_rng(17)
    level, p = 5, 17
    n = 1 << level
    me = jnp.asarray(rng.normal(size=(n, n, p)) + 1j * rng.normal(size=(n, n, p)),
                     jnp.complex64)
    assert _rel(ex.m2l_reference(me, level, p), ex.m2l_masked40(me, level, p)) < 1e-5


def test_fmm_end_to_end_with_kernels():
    """Full FMM with Pallas M2L + P2P == pure-jnp FMM."""
    rng = np.random.default_rng(3)
    pos = rng.uniform(0.02, 0.98, size=(1200, 2))
    gamma = rng.normal(size=1200)
    tree, _ = build_tree(pos, gamma, level=3, sigma=0.02)
    w_ref = np.asarray(fmm_velocity(tree, p=12, use_kernels=False))
    w_k = np.asarray(fmm_velocity(tree, p=12, use_kernels=True))
    assert _rel(w_k, w_ref) < 1e-5


def test_fmm_end_to_end_with_kernels_p17():
    """use_kernels=True vs reference at p=17 to 1e-5 relative error."""
    rng = np.random.default_rng(4)
    pos = rng.uniform(0.02, 0.98, size=(1500, 2))
    gamma = rng.normal(size=1500)
    tree, _ = build_tree(pos, gamma, level=4, sigma=0.02)
    w_ref = np.asarray(fmm_velocity(tree, p=17, use_kernels=False))
    w_k = np.asarray(fmm_velocity(tree, p=17, use_kernels=True))
    assert _rel(w_k, w_ref) < 1e-5


# ---------------------------------------------------------------------------
# Plan-aware block autotuning + lane padding (numerics-free, DESIGN.md §5/§9)
# ---------------------------------------------------------------------------


def test_autotune_block_table_and_clipping():
    from repro.kernels.ops import BLOCK_TABLE, autotune_block

    assert autotune_block(1, 32) == (1, 32)          # rim row strip, clipped
    assert autotune_block(2, 64) == BLOCK_TABLE["rim_row"]
    assert autotune_block(64, 2) == (BLOCK_TABLE["rim_col"][0], 2)  # clipped
    assert autotune_block(3, 3) == (3, 3)            # small tile, clipped
    assert autotune_block(32, 32) == BLOCK_TABLE["tile"]
    assert autotune_block(8, 64) == BLOCK_TABLE["wide"]
    by, bx = autotune_block(1, 1)
    assert by >= 1 and bx >= 1
    # every column block is whole sublane tiles (the TPU halo-window rule)
    assert all(bx % 8 == 0 for _, bx in BLOCK_TABLE.values())


@pytest.mark.parametrize("rows,cols,block,want", [
    # one column block: the window spans the whole halo'd width
    (64, 2, (32, 2), dict(BY=32, BX=8, cols_p=8, win_cols=10, halo_cols=10)),
    (4, 16, (8, 16), dict(BY=4, BX=16, cols_p=16, win_cols=18, halo_cols=18)),
    # several column blocks: 8-aligned windows of BX + 8 columns
    (512, 512, (8, 16), dict(BY=8, BX=16, cols_p=512, win_cols=24,
                             halo_cols=520)),
    (5, 20, (3, 5), dict(BY=3, BX=8, rows_p=6, cols_p=24, win_cols=16,
                         halo_cols=32)),
])
def test_halo_tiling_is_tpu_legal(rows, cols, block, want):
    """Column windows are whole sublane tiles or the whole array extent."""
    from repro.kernels.halo import halo_tiling
    t = halo_tiling(rows, cols, block)
    for k, v in want.items():
        assert getattr(t, k) == v, (k, getattr(t, k), v)
    assert t.BX % 8 == 0
    assert t.win_cols % 8 == 0 or t.win_cols == t.halo_cols
    assert t.halo_cols >= t.cols_p + 2        # the last window is in bounds


def test_p2p_pair_block_bounds_pair_tensor():
    """Blocks come out whole (8, 128) tiles, or (8, 8) in the interpreter,
    and one (1, BY, BX) f32 pair tensor fits PAIR_BYTES."""
    from repro.kernels.p2p import PAIR_BYTES, pair_block
    assert pair_block((8, 16)) == (8, 128)            # columns: whole lanes
    assert pair_block((4, 4), lanes=8) == (8, 8)      # interpreter
    assert pair_block((16, 128)) == (16, 128)         # as asked
    for block in ((64, 1024), (1024, 64), (1, 1)):
        by, bx = pair_block(block)
        assert by % 8 == 0 and bx % 128 == 0
        assert by * bx * 4 <= PAIR_BYTES


def test_m2l_lane_pad_and_autotune_block_equivalence():
    """lane_pad (4p -> 128 lanes), block=None autotuning, and non-dividing
    explicit blocks all reproduce the default launch bit-for-bit in f32."""
    rng = np.random.default_rng(21)
    level, p = 4, 7                                   # 4p = 28, pads to 128
    n = 1 << level
    me = jnp.asarray(rng.normal(size=(n, n, p)) + 1j * rng.normal(size=(n, n, p)),
                     jnp.complex64)
    me_halo = jnp.pad(me, ((2, 2), (0, 0), (0, 0)))
    base = np.asarray(m2l_pallas_slab(me_halo, level, p, block=(4, 4)))
    padded = np.asarray(m2l_pallas_slab(me_halo, level, p, block=(4, 4),
                                        lane_pad=True))
    np.testing.assert_allclose(padded, base, rtol=1e-6, atol=1e-6)
    from repro.kernels import ops as kops
    auto = np.asarray(kops.m2l_apply_slab(me_halo, level, p, lane_pad=False))
    np.testing.assert_allclose(auto, base, rtol=1e-6, atol=1e-6)
    auto_pad = np.asarray(kops.m2l_apply_slab(me_halo, level, p,
                                              lane_pad=True))
    np.testing.assert_allclose(auto_pad, base, rtol=1e-6, atol=1e-6)
    # non-dividing blocks; BX rounds up to 8 columns, and BY * BX stays
    # under 56 GEMM rows, where XLA:CPU's dot changes its summation order
    for blk in ((3, 5), (5, 3)):
        odd = np.asarray(m2l_pallas_slab(me_halo, level, p, block=blk))
        np.testing.assert_allclose(odd, base, rtol=1e-6, atol=1e-6)


def _pallas_out_shapes(fn, *args):
    """Output shapes of every ``pallas_call`` that ``fn`` traces."""
    shapes = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                shapes.extend(v.aval.shape for v in e.outvars)
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return shapes


def test_p2p_lane_pad_and_autotune_block_equivalence():
    """``lane_pad`` pads the lane axis, the box columns, to 128 and leaves
    the slots unpadded on their leading axis; it, block=None autotuning and
    non-dividing blocks all reproduce the default launch."""
    rng = np.random.default_rng(22)
    ny, nx, s = 6, 12, 5                       # 12 columns pad to 128 lanes
    z = jnp.asarray(rng.uniform(size=(ny, nx, s)) + 1j * rng.uniform(size=(ny, nx, s)),
                    jnp.complex64)
    q = jnp.asarray(rng.normal(size=(ny, nx, s)) + 0j, jnp.complex64)
    mask = jnp.asarray(rng.uniform(size=(ny, nx, s)) > 0.3)
    base = np.asarray(p2p_pallas(z, q, mask, sigma=0.05, block=(4, 4)))
    padded = np.asarray(p2p_pallas(z, q, mask, sigma=0.05, block=(4, 4),
                                   lane_pad=True))
    for lane_pad, cols in ((False, 16), (True, 128)):
        launch = _pallas_out_shapes(
            lambda *a: p2p_pallas(*a, sigma=0.05, block=(4, 4),
                                  lane_pad=lane_pad), z, q, mask)
        assert set(launch) == {(s, 8, cols)}     # (slots, rows_p, cols_p)
    m = np.asarray(mask)
    np.testing.assert_allclose(np.where(m, padded, 0), np.where(m, base, 0),
                               rtol=1e-6, atol=1e-6)
    from repro.kernels import ops as kops
    pad3 = ((1, 1), (1, 1), (0, 0))
    zh, qh, mh = (jnp.pad(z, pad3), jnp.pad(q, pad3), jnp.pad(mask, pad3))
    auto = np.asarray(kops.p2p_apply_slab(zh, qh, mh, 0.05, lane_pad=False))
    np.testing.assert_allclose(np.where(m, auto, 0), np.where(m, base, 0),
                               rtol=1e-6, atol=1e-6)
    auto_pad = np.asarray(kops.p2p_apply_slab(zh, qh, mh, 0.05,
                                              lane_pad=True))
    np.testing.assert_allclose(np.where(m, auto_pad, 0), np.where(m, base, 0),
                               rtol=1e-6, atol=1e-6)
    for blk in ((5, 3), (7, 7)):                      # non-dividing blocks
        odd = np.asarray(p2p_pallas(z, q, mask, sigma=0.05, block=blk))
        np.testing.assert_allclose(np.where(m, odd, 0), np.where(m, base, 0),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Flash attention kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,H,Hkv,T,d", [
    (2, 4, 4, 128, 32),     # MHA
    (1, 8, 2, 256, 64),     # GQA 4:1
    (2, 4, 1, 128, 64),     # MQA
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, Hkv, T, d, causal):
    rng = np.random.default_rng(H * T + d)
    q = jnp.asarray(rng.normal(size=(B, H, T, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, T, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, T, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    expect = ref.attention_ref(q, k, v, causal=causal)
    assert _rel(out, expect) < 2e-5


def test_flash_attention_bf16_and_blocks():
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.bfloat16)
    expect = ref.attention_ref(q, k, v, causal=True)
    for bq, bk in ((128, 64), (64, 128), (256, 256)):
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
        assert _rel(out.astype(np.float32), expect.astype(np.float32)) < 2e-2


def test_flash_attention_cross_attention_shapes():
    """S != T (prefill chunking / encoder-decoder style)."""
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 192, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 192, 32)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    expect = ref.attention_ref(q, k, v, causal=False)
    assert _rel(out, expect) < 2e-5
