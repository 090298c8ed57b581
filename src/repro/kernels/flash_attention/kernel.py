"""Pallas TPU flash attention: blocked online-softmax, GQA-aware BlockSpecs.

VMEM tiling: one (BQ, hd) query block per grid step; K/V delivered per
(batch, q-head) with the kv-head index derived IN THE INDEX MAP (h // G), so
grouped-query attention never materializes repeated K/V in HBM or VMEM.
Inside the kernel a fori_loop walks kv blocks with running (m, l, acc)
online-softmax state - the FlashAttention recurrence - entirely in VREGs/
VMEM.  Supports causal masking, sliding windows (Gemma-2 local layers) and
logit soft-capping.

MXU alignment: BQ and BKV default to 128/256 multiples; hd in {64, 128}.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1.0e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, window, cap,
            block_kv, seq_kv, q_offset):
    # q_ref: (BQ, hd); k_ref/v_ref: (Skv, hd); o_ref: (BQ, hd)
    bq, hd = q_ref.shape
    q = q_ref[...].astype(jnp.float32) * scale
    qi = pl.program_id(2)
    q_pos = q_offset + qi * bq + jax.lax.iota(jnp.int32, bq)

    n_kv = seq_kv // block_kv

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if cap is not None:
            s = cap * jnp.tanh(s / cap)
        k_pos = j * block_kv + jax.lax.iota(jnp.int32, block_kv)
        ok = jnp.ones((bq, block_kv), bool)
        if causal:
            ok &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            ok &= k_pos[None, :] > (q_pos[:, None] - window)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, scale: float, causal: bool = True,
                           window: Optional[int] = None,
                           cap: Optional[float] = None,
                           block_q: int = 128, block_kv: int = 128,
                           q_offset: int = 0,
                           interpret: bool = True):
    """q: (B, H, Sq, hd); k/v: (B, Hkv, Skv, hd) -> (B, H, Sq, hd)."""
    b, h, sq, hd = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = h // hkv
    assert sq % block_q == 0 and skv % block_kv == 0
    grid = (b, h, sq // block_q)
    # None-squeezed batch/head dims: kernel refs are 2-D (seq, hd) blocks.
    q_spec = pl.BlockSpec((None, None, block_q, hd),
                          lambda bi, hi, qi: (bi, hi, qi, 0))
    # GQA: the kv-head index comes from the INDEX MAP - no repeat in memory.
    kv_spec = pl.BlockSpec((None, None, skv, hd),
                           lambda bi, hi, qi: (bi, hi // g, 0, 0))
    o_spec = pl.BlockSpec((None, None, block_q, hd),
                          lambda bi, hi, qi: (bi, hi, qi, 0))

    def kern(q_ref, k_ref, v_ref, o_ref):
        _kernel(q_ref, k_ref, v_ref, o_ref, scale=scale, causal=causal,
                window=window, cap=cap, block_kv=block_kv, seq_kv=skv,
                q_offset=q_offset)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)
