"""Pallas TPU kernel: blockwise online-softmax (flash) attention.

Used by the LM substrate for train/prefill attention so the (T, S) score
matrix never materializes in HBM — required for the ``prefill_32k`` shapes
(32768^2 scores/head would be ~4 GiB/head/layer).

Structure (the canonical TPU flash pattern):
  * grid = (batch*heads, q_blocks, kv_blocks); the kv axis is minor-most so
    the output block for a given (bh, iq) is revisited across kv iterations
    and stays resident in VMEM;
  * running max ``m``, normalizer ``l`` and the unnormalized accumulator are
    carried in output refs (revisited blocks), initialized at ik == 0 and
    finalized (division) at the last kv block;
  * GQA is handled with *index arithmetic* in the k/v BlockSpec index_map
    (no materialized head repeat): kv row = (bh // H) * Hkv + (bh % H) // g;
  * causal blocks strictly above the diagonal are skipped via ``pl.when``.

VMEM budget per program: q(bq,d) + k/v(bk,d) + scores(bq,bk) + acc(bq,d);
bq = bk = 128..512 with d = 64..256 stays well under 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
               scale: float, causal: bool, bq: int, bk: int, nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _block():
        q = q_ref[0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos > qpos, NEG_INF, s)
        m_prev = m_ref[0]                              # (bq,)
        l_prev = l_ref[0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        palpha = jnp.exp(s - m_new[:, None])
        l_ref[0] = l_prev * alpha + palpha.sum(axis=-1)
        o_ref[0] = o_ref[0] * alpha[:, None] + \
            jnp.dot(palpha, v, preferred_element_type=jnp.float32)
        m_ref[0] = m_new

    if causal:
        # skip kv blocks strictly above the causal diagonal
        pl.when(ik * bk <= iq * bq + bq - 1)(_block)
    else:
        _block()

    @pl.when(ik == nk - 1)
    def _fin():
        l = l_ref[0]
        o_ref[0] = o_ref[0] / jnp.where(l > 0, l, 1.0)[:, None]


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128,
                    interpret: bool | None = None) -> jnp.ndarray:
    """q: (B, H, T, d); k, v: (B, Hkv, S, d) with H % Hkv == 0 -> (B, H, T, d).

    ``interpret=None`` runs the Pallas interpreter only off-TPU."""
    if interpret is None:
        from .ops import _interpret
        interpret = _interpret()
    B, H, T, d = q.shape
    _, Hkv, S, _ = k.shape
    assert H % Hkv == 0
    group = H // Hkv
    scale = 1.0 / (d ** 0.5)

    bq = min(block_q, T)
    bk = min(block_k, S)
    assert T % bq == 0 and S % bk == 0, (T, bq, S, bk)
    nq, nk = T // bq, S // bk

    qf = q.reshape(B * H, T, d)
    kf = k.reshape(B * Hkv, S, d)
    vf = v.reshape(B * Hkv, S, d)

    def kv_row(bh):
        return (bh // H) * Hkv + (bh % H) // group

    grid = (B * H, nq, nk)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (kv_row(b), j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (kv_row(b), j, 0)),
    ]
    out_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
        pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B * H, T, d), jnp.float32),
        jax.ShapeDtypeStruct((B * H, T), jnp.float32),
        jax.ShapeDtypeStruct((B * H, T), jnp.float32),
    ]
    kern = functools.partial(_fa_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, nk=nk)
    o, _, _ = pl.pallas_call(kern, grid=grid, in_specs=in_specs,
                             out_specs=out_specs, out_shape=out_shape,
                             interpret=interpret)(qf, kf, vf)
    return o.reshape(B, H, T, d).astype(q.dtype)
