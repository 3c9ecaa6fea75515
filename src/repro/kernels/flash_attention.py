"""Pallas TPU kernel: fused causal/sliding-window flash attention (fwd).

The dry-run baselines show attention *score* tensors dominate HBM traffic
at 4k–32k sequence lengths (§Perf iteration 1): the pure-JAX blocked
attention writes (qb × kb) f32 score blocks to HBM every step; this kernel
keeps them in VMEM — per-block traffic drops from O(qb·kb) to
O((qb + kb)·hd).

Layout: grid (B·KV·G, q_blocks, kv_blocks), kv innermost (sequential on
TPU → the online-softmax accumulators live across steps in VMEM scratch):

    q: (B·KV·G, T, hd)  block (1, qb, hd)  index (i, qi)
    k: (B·KV, S, hd)    block (1, kb, hd)  index (i // G, ki)   [GQA share]
    v: like k
    o: like q, written at the last kv step

Causal + window masks come from absolute positions derived from block
indices.  MXU dims (qb, hd, kb) are multiples of 128 at production block
sizes (512, 128, 512); VMEM footprint ≈ (qb + 2·kb + 2·qb)·hd·4B ≈ 1 MiB.

Backward runs through the reference path (the models use this kernel via
``jax.custom_vjp`` with recompute), so train cells benefit in the
recomputed forward while prefill/serve get the full win.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime.device import pallas_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  kv_steps: int, q_block: int, kv_block: int, window: int,
                  scale: float):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (qb, hd)
    k = k_ref[0].astype(jnp.float32)            # (kb, hd)
    v = v_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * corr
                    + jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _flash_fwd(q, k, v, groups: int, *, window: int, q_block: int,
               kv_block: int, interpret: bool):
    """q: (B·KV·G, T, hd); k, v: (B·KV, S, hd)."""
    bkg, t, hd = q.shape
    s_len = k.shape[1]
    scale = hd ** -0.5
    qb = min(q_block, t)
    while t % qb:
        qb //= 2
    kb = min(kv_block, s_len)
    while s_len % kb:
        kb //= 2
    n_q, n_k = t // qb, s_len // kb
    g = groups

    out = pl.pallas_call(
        functools.partial(_flash_kernel, kv_steps=n_k, q_block=qb,
                          kv_block=kb, window=window, scale=scale),
        grid=(bkg, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, qb, hd), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, kb, hd), lambda i, qi, ki: (i // g, ki, 0)),
            pl.BlockSpec((1, kb, hd), lambda i, qi, ki: (i // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, qb, hd), lambda i, qi, ki: (i, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),    # m
            pltpu.VMEM((qb, 1), jnp.float32),    # l
            pltpu.VMEM((qb, hd), jnp.float32),   # acc
        ],
        interpret=interpret,
    )(q, k, v)
    return out


def flash_attention_kernel(q, k, v, *, window: int = 0,
                           q_block: int = 512, kv_block: int = 512,
                           interpret: bool | None = None):
    """Drop-in flash core.  q: (B, T, H, hd); k, v: (B, S, KV, hd) with
    self-attention positions (0..T−1 == 0..S−1).  Returns (B, T, H, hd)."""
    if interpret is None:
        interpret = pallas_interpret()
    b, t, h, hd = q.shape
    s_len, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qg = (q.reshape(b, t, kvh, g, hd).transpose(0, 2, 3, 1, 4)
          .reshape(b * kvh * g, t, hd))
    kg = k.transpose(0, 2, 1, 3).reshape(b * kvh, s_len, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(b * kvh, s_len, hd)
    og = _flash_fwd(qg, kg, vg, g, window=window, q_block=q_block,
                    kv_block=kv_block, interpret=interpret)
    return (og.reshape(b, kvh, g, t, hd).transpose(0, 3, 1, 2, 4)
            .reshape(b, t, h, hd))
