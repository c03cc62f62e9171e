"""Pallas TPU kernel: fused NetMax two-step update (gossip_mix).

The consensus update  out = (1-w) * (x + u) + w * pulled  (Alg. 2 lines
11+13-15, with u = optimizer delta) is pure HBM traffic: naively it is three
elementwise passes (apply update, subtract, mix) over every parameter.  The
fused kernel streams x, u, pulled through VMEM once:

    reads  3 x bytes   writes 1 x bytes      (vs 5R/3W unfused)

which at 819 GB/s HBM is the dominant non-matmul cost of a NetMax round at
small per-worker batch.  Block layout: flat 1-D tiles of 64k elements (f32)
— bandwidth-bound, no MXU alignment needed, lane-dim 128-aligned.

Two entry points share the kernel body:

* ``gossip_mix``       — one replica, scalar w (the trainer's per-slice path)
* ``gossip_mix_rows``  — a stacked (R, ...) block with per-row weights, one
  grid row per worker/cohort member (the batched engine / stacked trainer
  path; w lives in SMEM indexed by the row program id).  Rows are tiled as
  (k, 128) with k a multiple of the dtype's sublane count, which the v5e
  compiler accepts for any R (tests/test_tpu_compile.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK = 65536  # elements per tile (256 KiB f32 in VMEM x 4 buffers)


def _mix_kernel(x_ref, u_ref, p_ref, w_ref, o_ref):
    w = w_ref[0]
    x_half = x_ref[...].astype(jnp.float32) + u_ref[...].astype(jnp.float32)
    out = (1.0 - w) * x_half + w * p_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def gossip_mix(x, u, pulled, w, *, interpret: bool = False, block: int = _BLOCK):
    """out = (1-w)*(x+u) + w*pulled, elementwise; w scalar (per worker).

    x/u/pulled: same-shape arrays (any dtype); w: f32 scalar array.
    """
    shape, dtype = x.shape, x.dtype
    n = x.size
    xf, uf, pf = (a.reshape(-1) for a in (x, u, pulled))
    pad = (-n) % block
    if pad:
        xf = jnp.pad(xf, (0, pad))
        uf = jnp.pad(uf, (0, pad))
        pf = jnp.pad(pf, (0, pad))
    nb = xf.size // block
    wv = jnp.asarray(w, jnp.float32).reshape(1)

    out = pl.pallas_call(
        _mix_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((xf.size,), dtype),
        interpret=interpret,
    )(xf, uf, pf, wv)
    return out[:n].reshape(shape)


_LANES = 128


def _mix_rows_kernel(x_ref, u_ref, p_ref, w_ref, o_ref):
    w = w_ref[pl.program_id(0)]  # this grid row's weight (SMEM)
    x_half = x_ref[...].astype(jnp.float32) + u_ref[...].astype(jnp.float32)
    out = (1.0 - w) * x_half + w * p_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def gossip_mix_rows(x, u, pulled, w, *, interpret: bool = False, block: int = _BLOCK):
    """Per-row fused mix: out[r] = (1-w[r])*(x[r]+u[r]) + w[r]*pulled[r].

    x/u/pulled: (R, ...) same-shape stacked arrays (any dtype); w: (R,) f32.
    Each row is laid out as (sublanes, 128) and the grid is (rows, tiles):
    each program streams one (k, 128) tile of one row through VMEM, the row
    dim squeezed out of the block, with all R weights resident in SMEM.  k
    is a multiple of the dtype's sublane count (8 for f32, 16 for bf16), so
    the chip's (8, 128) tiling rule holds for any R.  The batched engine
    thus mixes a whole cohort in one kernel launch instead of R separate
    ``gossip_mix`` calls.
    """
    shape, dtype = x.shape, x.dtype
    R = shape[0]
    n = x.size // max(R, 1)
    sub = max(8, 32 // jnp.dtype(dtype).itemsize)
    rows = -(-n // _LANES)
    # Shrink the tile for small rows so padding never dominates; n is static
    # under jit, so this is trace-time arithmetic.
    k = min(max(sub, block // _LANES // sub * sub), -(-rows // sub) * sub)
    nb = -(-rows // k)
    pad = nb * k * _LANES - n
    xf, uf, pf = (
        jnp.pad(a.reshape(R, n), ((0, 0), (0, pad))).reshape(R, nb * k, _LANES)
        for a in (x, u, pulled)
    )
    wv = jnp.asarray(w, jnp.float32).reshape(R)

    tile = pl.BlockSpec((None, k, _LANES), lambda r, b: (r, b, 0))
    out = pl.pallas_call(
        _mix_rows_kernel,
        grid=(R, nb),
        in_specs=[tile, tile, tile, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((R, nb * k, _LANES), dtype),
        interpret=interpret,
    )(xf, uf, pf, wv)
    return out.reshape(R, -1)[:, :n].reshape(shape)
