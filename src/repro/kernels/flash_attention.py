"""Pallas TPU flash attention (forward): blockwise streaming softmax.

Grid: (batch*heads, Sq/block_q, Skv/block_kv) with the KV axis innermost
("arbitrary" semantics) — each (bh, qi) tile revisits its output while the
(m, l, acc) running-softmax state lives in VMEM scratch.  GQA is handled in
the k/v index_map (query head -> kv head).

BlockSpec tiling: q (1, block_q, D), k/v (1, block_kv, D), out (1, block_q, D).
With the default 512/512 blocks and D<=128 the VMEM working set
(q+k+v+p+acc in f32) is ~3.5 MB — comfortably inside the 16 MB v5e VMEM with
double buffering.

Validated in interpret mode against ref.flash_attention_ref (this container
is CPU-only); on TPU the same kernel replaces the lax.scan blockwise path via
``Recipe(attn_impl="pallas")``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, block_q: int, block_kv: int,
            seq_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal early-out: a KV block strictly above the diagonal contributes
    # nothing — skip its MXU work entirely.  Recovers the ~2x "causal waste"
    # the lax.scan blockwise path pays (EXPERIMENTS.md §Roofline: prefill
    # useful/HLO 0.56-0.76), which XLA cannot skip with static shapes.
    live = (not causal) or (ki * block_kv <= qi * block_q + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # (bq, D)
        k = k_ref[0].astype(jnp.float32)                   # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq_kv
        if causal:
            mask &= q_pos >= k_pos
        s = jnp.where(mask, s, _NEG_INF)

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


def _kv_index_map(h: int, hk: int):
    g = h // hk

    def index_map(bh, qi, ki):
        batch = bh // h
        head = bh % h
        return batch * hk + head // g, ki, 0

    return index_map


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_kv: int = 512, interpret: bool = False):
    """q: (B,Sq,H,D); k/v: (B,Skv,Hk,D). Returns (B,Sq,H,D)."""
    b, sq, h, d = q.shape
    skv, hk = k.shape[1], k.shape[2]
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    pad_q = (-sq) % block_q
    pad_kv = (-skv) % block_kv
    qq = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    kk = jnp.moveaxis(k, 2, 1).reshape(b * hk, skv, d)
    vv = jnp.moveaxis(v, 2, 1).reshape(b * hk, skv, d)
    if pad_q:
        qq = jnp.pad(qq, ((0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        kk = jnp.pad(kk, ((0, 0), (0, pad_kv), (0, 0)))
        vv = jnp.pad(vv, ((0, 0), (0, pad_kv), (0, 0)))
    grid = (b * h, (sq + pad_q) // block_q, (skv + pad_kv) // block_kv)

    kernel = functools.partial(
        _kernel, scale=1.0 / (d ** 0.5), causal=causal,
        block_q=block_q, block_kv=block_kv, seq_kv=skv)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, d), _kv_index_map(h, hk)),
            pl.BlockSpec((1, block_kv, d), _kv_index_map(h, hk)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq + pad_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qq, kk, vv)
    out = out[:, :sq].reshape(b, h, sq, d)
    return jnp.moveaxis(out, 1, 2)
