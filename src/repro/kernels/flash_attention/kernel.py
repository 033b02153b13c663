"""Flash attention (forward) Pallas TPU kernel.

Online-softmax tiling adapted to the TPU memory hierarchy: q blocks stay
resident in VMEM; k/v stream through VMEM blocks along the innermost grid
dimension; the (m, l, acc) running state lives in VMEM scratch across the
k-block iterations (grid semantics: k dimension is "arbitrary" = sequential
on TPU).  Block shapes default to MXU-aligned (128, d_head).

Layout: q, k, v are [B*H, S, d] (heads flattened into the grid's parallel
dimension — GQA repetition is done by the ops wrapper via index mapping,
not materialization).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q: int, block_k: int, seq_k: int, causal: bool,
                  sm_scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # [block_q, d]
    k = k_ref[0]                                   # [block_k, d]
    v = v_ref[0]                                   # [block_k, d]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    if causal:
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = True,
                    kv_map=None) -> jnp.ndarray:
    """q [BH, Sq, d]; k, v [BHkv, Sk, d].  kv_map: callable mapping a q-head
    grid index to its kv-head index (GQA) — defaults to identity."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    grid = (bh, sq // block_q, sk // block_k)
    kvm = kv_map or (lambda h: h)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, seq_k=sk,
        causal=causal, sm_scale=d ** -0.5)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, iq, ik: (kvm(h), ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, iq, ik: (kvm(h), ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
