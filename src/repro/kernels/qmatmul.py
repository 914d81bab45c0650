"""Pallas TPU kernel: mixed-precision dequantize-matmul.

This carries the paper's dual approximation to the LM architectures
(DESIGN.md §5): weights are stored at low precision (2..8-bit codes held in
int8) after hardware-friendly value snapping, with one scale per output
channel — the LM analogue of the per-comparator (precision, substituted
threshold) genes. The kernel fuses dequantization into a blocked matmul so
low-bit weights never round-trip through HBM at f32 width.

Classic 3-D blocked matmul: grid (m_blocks, n_blocks, k_blocks), K innermost
("arbitrary") with a VMEM f32 accumulator; MXU-aligned 128x tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, scale_ref, out_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)            # (bm, bk)
    w = w_ref[...].astype(jnp.float32)            # (bk, bn) int8 codes -> f32
    acc_ref[...] += jax.lax.dot(x, w, precision=jax.lax.Precision.HIGHEST)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        out_ref[...] = acc_ref[...] * scale_ref[...]   # (1, bn) broadcast


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def qmatmul(
    x,        # (M, K) f32/bf16 activations
    w_q,      # (K, N) int8 quantized codes (2..8-bit range, snapped)
    scale,    # (1, N) f32 per-output-channel dequant scale
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
):
    m, k = x.shape
    _, n = w_q.shape
    n_k = k // block_k
    grid = (m // block_m, n // block_n, n_k)
    kernel = functools.partial(_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w_q, scale)
