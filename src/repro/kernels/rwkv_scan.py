"""Pallas kernel: chunked RWKV-6 WKV recurrence.  Runs in interpret mode only:
the TPU compiler refuses it ("Unimplemented primitive in Pallas TPU lowering:
cumsum", for a described v5e), and no model path calls it (models/rwkv.py runs
the jnp scan).

Chunked form of the data-dependent-decay recurrence (DESIGN.md §3): the
per-token update

    y_t   = r_t (S_{t-1} + u k_t v_t^T)
    S_t   = diag(w_t) S_{t-1} + k_t v_t^T

is reformulated in CHUNK form so the MXU does the work.  For a chunk of C
tokens with per-token decays w, define cumulative decays
A_i = prod_{j<=i} diag(w_j).  Then within a chunk:

    y_i = r_i A_{i-1} S_0  +  sum_{j<i} r_i (A_{i-1}/A_j) (k_j v_j^T)
                            +  r_i (u k_i v_i^T)
        = (r_i A_{i-1}) S_0 + sum_j [(r_i A_{i-1}/A_j) k_j] 1[j<i] v_j + u-term
    S_C = A_C S_0 + sum_j (A_C / A_j) k_j v_j^T

which is two (C x N) x (N x N) matmuls + a (C x C) masked score matmul —
exactly flash-attention-shaped compute with decay-weighted scores.  The
kernel walks chunks sequentially (grid dim 1) carrying S in VMEM scratch;
each (batch*head) is an independent grid row.

Numerical care: A ratios are computed in log space (log w <= 0) and
exponentiated at use; f32 accumulation throughout.  The factored matmul form
computes exp(+La) * exp(-La) pairs that cancel analytically but can overflow
f32 when the per-chunk cumulative decay passes ~e^-75; the wrapper therefore
clamps per-step log-decay to >= -(75/chunk).  Contributions whose true decay
is stronger than that are below f32 resolution anyway (error <= e^-75 per
pair) — the allclose tests cover both trained-range decays (no clamp active)
and the extreme-decay clamped semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_SUB = 16  # sub-chunk length: bounds exp() exponent ranges for f32 accuracy


def _rwkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0].astype(jnp.float32)  # (1, N) bonus
    sub = min(_SUB, chunk)

    # Process the VMEM block in sub-chunks: the factored matmul form computes
    # exp(+La)*exp(-La) pairs whose f32 rounding error grows like
    # exp(|decay range|); sub-chunking bounds the range (DESIGN.md kernels).
    for s0 in range(0, chunk, sub):
        r = r_ref[0, s0 : s0 + sub].astype(jnp.float32)  # (c, N)
        k = k_ref[0, s0 : s0 + sub].astype(jnp.float32)
        v = v_ref[0, s0 : s0 + sub].astype(jnp.float32)
        lw = lw_ref[0, s0 : s0 + sub].astype(jnp.float32)
        S = s_ref[...]  # (N, N) carry

        # cumulative log decay INCLUSIVE: La[i] = sum_{j<=i} lw[j]
        La = jnp.cumsum(lw, axis=0)  # (c, N)
        r_dec = r * jnp.exp(La - lw)  # r_i A_{i-1}
        k_inv = k * jnp.exp(-La)  # k_j / A_j
        scores = jax.lax.dot_general(
            r_dec, k_inv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (c, c)
        row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(row > col, scores, 0.0)
        diag = jnp.sum(r * u * k, axis=1)  # (c,) u-bonus on the diagonal
        y = (
            jax.lax.dot_general(r_dec, S, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
            + diag[:, None] * v
        )
        # state update: S <- diag(A_c) S + sum_j diag(A_c/A_j) k_j v_j^T
        A_C = jnp.exp(La[-1])  # (N,)
        k_scaled = k_inv * A_C[None, :]
        s_ref[...] = A_C[:, None] * S + jax.lax.dot_general(
            k_scaled, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        o_ref[0, s0 : s0 + sub] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv_scan(
    r: jnp.ndarray,  # (B, S, H, N)
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,  # (B, S, H, N) decays in (0, 1)
    u: jnp.ndarray,  # (H, N)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns y (B, S, H, N) == the sequential WKV recurrence output."""
    B, S, H, N = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, N)

    rr, kk, vv = fold(r), fold(k), fold(v)
    lw_bound = 75.0 / min(_SUB, chunk)  # f32-safe exponent range (module doc)
    lw = fold(
        jnp.clip(jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-30)), -lw_bound, 0.0)
    )
    uu = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, 1, N)

    out = pl.pallas_call(
        functools.partial(_rwkv_kernel, chunk=chunk),
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, N), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, N), r.dtype),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(rr, kk, vv, lw, uu)

    return out.reshape(B, H, S, N).transpose(0, 2, 1, 3)
