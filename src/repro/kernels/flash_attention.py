"""Pallas TPU kernel: causal/windowed GQA flash attention (the LM hot loop).

Online-softmax attention with VMEM-resident accumulators.  Grid is
(batch, q_heads, q_blocks, kv_blocks); the kv axis is the innermost
(sequential) dimension so the m/l/acc scratch carries across kv blocks.
Blocks entirely above the causal diagonal, or entirely left of the sliding
window, are skipped — the kernel-level realization of the sub-quadratic
windowed archs (mixtral SWA).

Block shapes: q/o (bq, d), k/v (bk, d) with d padded to a lane multiple;
masked logits use a large-negative finite sentinel (−1e30) so fully-masked
prefixes flush out of the accumulator when the first real block arrives
(α = exp(m_prev − m_new) underflows to 0), avoiding −inf NaNs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1.0e30


def _flash_kernel(causal: bool, window: Optional[int], scale: float,
                  bq: int, bk: int, nk: int,
                  q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = jnp.bool_(True)
    if causal:
        run &= ki * bk < (qi + 1) * bq          # not entirely above diagonal
    if window is not None:
        run &= (ki + 1) * bk - 1 > qi * bq - window  # not entirely left of window

    @pl.when(run)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, _NEG)

        m_prev = m_scr[...]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
        p = jnp.exp(s - m_new)                            # (bq, bk)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)                   # fully-masked rows → 0 output
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "sm_scale", "block_q", "block_k", "interpret"),
)
def flash_attention_p(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: Optional[int] = None,
                      sm_scale: Optional[float] = None,
                      block_q: int = 128, block_k: int = 128,
                      interpret: bool) -> jax.Array:
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D); Hq % Hkv == 0 → (B, Hq, S, D)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nq, nk = s // bq, s // bk
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)

    kernel = functools.partial(_flash_kernel, causal, window, scale, bq, bk, nk)
    return pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, qi, ki: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, h, qi, ki: (bb, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, h, qi, ki: (bb, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bb, h, qi, ki: (bb, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
