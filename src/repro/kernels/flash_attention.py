"""Pallas TPU flash attention (GQA, causal) with explicit VMEM BlockSpecs.

TPU adaptation of the paper's compute hot spot (train_4k / prefill_32k):
blocked online-softmax with the KV loop as the innermost grid dimension,
tile shapes aligned to the MXU (128-multiples), accumulators resident in
VMEM scratch across KV steps.  Grid: (batch*kv_heads, q_blocks, kv_blocks);
the KV dimension iterates fastest so the (acc, m, l) scratch carries across
kv steps for one (bh, q_block).

Checked against ref.reference_attention in interpret mode (CPU).  The v5e
compiler accepts it at qwen1.5-0.5b widths (MHA, S=4096;
tests/test_tpu_compile.py), but it has not run on a chip, and no model path
calls it: models/attention.py runs its own XLA chunked attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,  # VMEM refs
    acc_ref, m_ref, l_ref,  # scratch (VMEM)
    *, causal: bool, block_q: int, block_k: int, scale: float, G: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def compute():
        q = q_ref[0].astype(jnp.float32)  # (G*block_q, hd)
        k = k_ref[0].astype(jnp.float32)  # (block_k, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G*bq, bk)
        if causal:
            # q rows are s-major, g-minor: row r -> position offset r // G
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = (q_start + row) >= (k_start + col)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    if causal:
        # Skip KV blocks strictly in the future of the whole Q block.
        @pl.when(k_start <= q_start + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,  # (B, S, H, hd)
    k: jnp.ndarray,  # (B, Sk, Hk, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S, H, hd = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    G = H // Hk
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    assert S % block_q == 0 and Sk % block_k == 0

    # Layout: fold G into the q rows so one grid cell serves a whole KV head.
    # q: (B*Hk, G*S, hd) — rows [g*S + s]; kernel blocks are (G*block_q, hd)
    # covering the SAME s-range for all g (transpose to (s_block, g) order).
    qr = (
        q.reshape(B, S, Hk, G, hd)
        .transpose(0, 2, 1, 3, 4)  # (B, Hk, S, G, hd)
        .reshape(B * Hk, S, G, hd)
        .reshape(B * Hk, S * G, hd)
    )
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, hd)

    nq, nk = S // block_q, Sk // block_k
    grid = (B * Hk, nq, nk)
    scale = float(1.0 / (hd ** 0.5))

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, causal=causal, block_q=block_q, block_k=block_k, scale=scale, G=G
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G * block_q, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, G * block_q, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hk, S * G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * block_q, hd), jnp.float32),
            pltpu.VMEM((G * block_q,), jnp.float32),
            pltpu.VMEM((G * block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)

    # rows within a block are (block_q major? no: we built S*G as s-major of
    # G-contiguous rows) — restore (B, S, H, hd).
    out = (
        out.reshape(B, Hk, S, G, hd)
        .transpose(0, 2, 1, 3, 4)  # (B, S, Hk, G, hd)
        .reshape(B, S, H, hd)
    )
    return out
