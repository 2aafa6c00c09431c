"""Blockwise (flash) attention Pallas TPU kernel.

Online-softmax over KV blocks with f32 running stats in VMEM scratch.
Supports causal masking, sliding windows (gemma2 local layers, hymba),
logit soft-capping (gemma2) and GQA (kv-head sharing) — the feature set the
assigned archs need.  Block shapes are MXU-aligned (multiples of 128 in the
S dims whenever the sequence allows).

The paper connection: attention is the *activation-side* consumer in the
H2PIPE analogy — K/V blocks stream through VMEM exactly like the line
buffer holds the k_h rows in flight, while the weight path (stream_matmul)
handles the big deterministic tier.

Layout: q [B,H,Sq,hd]; k/v [B,KV,Sk,hd].  Grid (B, H, nq, nk), k innermost
(sequential); scratch (acc, m, l) persists across the k sweep.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                  l_ref, *, bq: int, bk: int, nk: int, causal: bool,
                  window: int, softcap: float, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _reset():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                                    # [bq, hd]
    k = k_ref[0, 0]                                    # [bk, hd]
    v = v_ref[0, 0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.max(s, axis=-1)[:, None]               # [bq,1]
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)[:, None]
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _store():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(denom))[:, 0]


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0, bq: int = 128,
                           bk: int = 128, interpret: bool = False,
                           return_lse: bool = False):
    """q: [B,H,Sq,hd]; k,v: [B,KV,Sk,hd] -> [B,H,Sq,hd] (and lse if
    requested — needed by the backward kernels)."""
    B, H, Sq, hd = q.shape
    _, KV, Sk, _ = k.shape
    hd_v = v.shape[-1]                   # may differ from hd (MLA: 192/128)
    assert H % KV == 0
    rep = H // KV
    bq, bk = min(bq, Sq), min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(hd)
    grid = (B, H, nq, nk)
    o, lse = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                          window=window, softcap=softcap, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki: (b, h // rep, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd_v),
                         lambda b, h, qi, ki: (b, h // rep, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd_v),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, qi, ki: (b, h, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, hd_v), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, hd_v), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                  "arbitrary")),
    )(q, k, v)
    return (o, lse) if return_lse else o


# ---------------------------------------------------------------------------
# backward kernels (flash-backward: recompute block scores, accumulate)
# ---------------------------------------------------------------------------


def _mask_and_scores(q, kb, q_pos, k_pos, *, causal, window, softcap, scale):
    s_raw = jnp.dot(q, kb.T, preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s_raw / softcap) * softcap
        dcap = 1.0 - (s / softcap) ** 2          # d s / d s_raw
    else:
        s, dcap = s_raw, None
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= q_pos - k_pos < window
    return jnp.where(mask, s, NEG_INF), mask, dcap


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, bq, bk, nk, causal, window,
                         softcap, scale):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)
    kb = k_ref[0, 0].astype(jnp.float32)
    vb = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s, mask, dcap = _mask_and_scores(q, kb, q_pos, k_pos, causal=causal,
                                     window=window, softcap=softcap,
                                     scale=scale)
    p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0][:, None]), 0.0)
    dp = jnp.dot(do, vb.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, 0][:, None])
    if softcap:
        ds = ds * dcap
    acc_ref[...] += jnp.dot(ds, kb, preferred_element_type=jnp.float32) \
        * scale

    @pl.when(ki == nk - 1)
    def _store():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, bq, bk, nq,
                          causal, window, softcap, scale):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32)
    kb = k_ref[0, 0].astype(jnp.float32)
    vb = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s, mask, dcap = _mask_and_scores(q, kb, q_pos, k_pos, causal=causal,
                                     window=window, softcap=softcap,
                                     scale=scale)
    p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0][:, None]), 0.0)
    dp = jnp.dot(do, vb.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, 0][:, None])
    if softcap:
        ds = ds * dcap
    dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32) \
        * scale
    dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _store():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal, window, softcap,
                        bq: int = 128, bk: int = 128,
                        interpret: bool = False):
    """dq, dk, dv for the flash kernel.  k/v enter repeated to H heads
    (GQA folding happens in the custom_vjp wrapper)."""
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    hd_v = v.shape[-1]
    bq, bk = min(bq, Sq), min(bk, Sk)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(hd)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    common = dict(causal=causal, window=window, softcap=softcap, scale=scale,
                  bq=bq, bk=bk)
    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0))
    o_spec = pl.BlockSpec((1, 1, bq, hd_v),
                          lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h, ki, 0))
    v_spec = pl.BlockSpec((1, 1, bk, hd_v),
                          lambda b, h, qi, ki: (b, h, ki, 0))
    lse_spec = pl.BlockSpec((1, 1, bq), lambda b, h, qi, ki: (b, h, qi))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, nk=nk, **common),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, v_spec, o_spec, lse_spec, lse_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                  "arbitrary")),
    )(q, k, v, do, lse, delta)

    q_spec2 = pl.BlockSpec((1, 1, bq, hd), lambda b, h, ki, qi: (b, h, qi, 0))
    o_spec2 = pl.BlockSpec((1, 1, bq, hd_v),
                           lambda b, h, ki, qi: (b, h, qi, 0))
    kv_spec2 = pl.BlockSpec((1, 1, bk, hd),
                            lambda b, h, ki, qi: (b, h, ki, 0))
    v_spec2 = pl.BlockSpec((1, 1, bk, hd_v),
                           lambda b, h, ki, qi: (b, h, ki, 0))
    lse_spec2 = pl.BlockSpec((1, 1, bq), lambda b, h, ki, qi: (b, h, qi))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, nq=nq, **common),
        grid=(B, H, nk, nq),
        in_specs=[q_spec2, kv_spec2, v_spec2, o_spec2, lse_spec2,
                  lse_spec2],
        out_specs=[kv_spec2, v_spec2],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd_v), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                  "arbitrary")),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
