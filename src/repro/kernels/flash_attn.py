"""Pallas TPU kernel: causal flash attention with GQA (the §Perf next-lever
for the prefill cells).

The jnp chunked attention in models/attention.py materializes (S, chunk)
score blocks in HBM on the XLA-CPU dry-run; this kernel keeps the running
(m, l, acc) softmax state and the score block in VMEM — the memory-term
upper bound in EXPERIMENTS.md §Roofline collapses to the q/k/v/o streams.

Layout: q (H, Sq, hd) with H = B * n_q_heads (flattened); k/v (Hkv, Skv, hd)
with GQA group factor G = H/Hkv resolved by the k/v index_map (q head h
reads kv head h // G). Grid = (H, q_blocks, kv_blocks), kv innermost
("arbitrary"); causal masking by absolute position; the out block is
finalized on the last kv step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_q: int, block_k: int, n_kv: int, scale: float,
            softcap: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, :, :].astype(jnp.float32)            # (bq, hd)
    k = k_ref[0, :, :].astype(jnp.float32)            # (bk, hd)
    sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST) * scale
    if softcap > 0:
        sc = jnp.tanh(sc / softcap) * softcap
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    sc = jnp.where(q_pos >= k_pos, sc, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, sc.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(sc - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    m_ref[...] = m_new
    pv = jax.lax.dot(p.astype(v_ref.dtype), v_ref[0, :, :],
                     precision=jax.lax.Precision.HIGHEST)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + pv.astype(jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, :, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group", "block_q", "block_k", "softcap", "interpret"))
def flash_attention(
    q,        # (H, Sq, hd)
    k,        # (Hkv, Skv, hd)
    v,        # (Hkv, Skv, hd)
    *,
    group: int = 1,          # H / Hkv
    block_q: int = 256,
    block_k: int = 256,
    softcap: float = 0.0,
    interpret: bool = False,
):
    h, sq, hd = q.shape
    _, skv, _ = k.shape
    n_q = sq // block_q
    n_kv = skv // block_k
    scale = hd ** -0.5
    grid = (h, n_q, n_kv)
    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, n_kv=n_kv, scale=scale,
        softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda hh, qi, ki: (hh, qi, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda hh, qi, ki, g=group: (hh // g, ki, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda hh, qi, ki, g=group: (hh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda hh, qi, ki: (hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
