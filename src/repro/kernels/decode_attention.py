"""Pallas TPU flash-decode: single-query GQA attention over a static KV cache.

Grid: (batch*heads, Skv/block_kv) — split-K over the cache with running
(m, l, acc) scratch, length-masked per batch element.  The q block is a
single row; VMEM traffic is dominated by streaming the KV cache once, which
is exactly the decode roofline (memory-bound).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention"]

_NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_kv: int, seq_kv: int):
    ki = pl.program_id(1)
    n_kv = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                       # (1, D)
    k = k_ref[0].astype(jnp.float32)                       # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = (k_pos < len_ref[0, 0]) & (k_pos < seq_kv)
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def decode_attention(q, k_cache, v_cache, lengths, *, block_kv: int = 512,
                     interpret: bool = False):
    """q: (B,1,H,D); caches: (B,S,Hk,D); lengths: (B,). Returns (B,1,H,D)."""
    b, _, h, d = q.shape
    skv, hk = k_cache.shape[1], k_cache.shape[2]
    g = h // hk
    block_kv = min(block_kv, skv)
    pad_kv = (-skv) % block_kv
    qq = q.reshape(b * h, 1, d)
    kk = jnp.moveaxis(k_cache, 2, 1).reshape(b * hk, skv, d)
    vv = jnp.moveaxis(v_cache, 2, 1).reshape(b * hk, skv, d)
    if pad_kv:
        kk = jnp.pad(kk, ((0, 0), (0, pad_kv), (0, 0)))
        vv = jnp.pad(vv, ((0, 0), (0, pad_kv), (0, 0)))
    lens = jnp.repeat(lengths.astype(jnp.int32), h).reshape(b * h, 1)
    grid = (b * h, (skv + pad_kv) // block_kv)

    def kv_map(bh, ki):
        return (bh // h) * hk + (bh % h) // g, ki, 0

    kernel = functools.partial(_kernel, scale=1.0 / (d ** 0.5),
                               block_kv=block_kv, seq_kv=skv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda bh, ki: (bh, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, block_kv, d), kv_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qq, kk, vv)
    return out.reshape(b, h, 1, d).transpose(0, 2, 1, 3)
