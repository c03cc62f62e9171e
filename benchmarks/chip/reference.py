"""Plain float32 reference of a NetMax round, for any model the benchmark
describes, and the blocks a model's own reference is built from.

The round is Alg. 2: a local SGD step with momentum and weight decay, then a
pull of the drawn neighbour's pre-round replica and the mix
``x + w (pulled - x)``.  The model is a module of ``models/`` (found by the
configuration's ``model_type``), whose ``seq_loss(d, quant, params, tokens,
labels)`` is the forward pass and loss of one sequence.  It imports nothing
of the program.

Every matrix product runs at ``precision=highest``.  To fit at the timed
sizes it works one sequence at a time, and a model's ``seq_loss`` recomputes
each layer in the backward pass and takes attention and the loss head in
blocks of ``BLOCK`` positions (``attention``, ``blocked_loss``); none of that
changes the mathematics.

The control is the reference one precision below the configuration's:
``quant="int8"`` (below bfloat16) rounds each matrix product's operands to
symmetric int8 with one scale per tensor (straight-through in the backward
pass); ``quant="bf16"`` (below float32) rounds them to bfloat16 and keeps
the weights in bfloat16 between updates.  A model's products go through
``mm`` so that the control reaches all of them.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512
HI = jax.lax.Precision.HIGHEST
# The control for a configuration's precision: the one below it.
CONTROL = {"bfloat16": "int8", "float32": "bf16"}


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return x + jax.lax.stop_gradient(jnp.round(x / s) * s - x)


def mm(spec, a, b, quant):
    """``einsum(spec, a, b)`` at ``highest``, or in the control's precision."""
    if quant == "int8":
        a, b = _q8(a), _q8(b)
    if quant == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=HI)


def _store(x, quant):
    # reduce_precision, not a pair of casts, which XLA may drop as excess precision
    return jax.lax.reduce_precision(x, 8, 7) if quant == "bf16" else x


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (S, H, hd); rotate_half form, as the published model applies it."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(q, k, v, quant):
    """Causal GQA for one sequence; q (S, H, hd), k/v (S, Hk, hd)."""
    S, H, hd = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)  # head h reads kv head h // G
    v = jnp.repeat(v, G, axis=1)
    blk = min(BLOCK, S)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        s = mm("qhd,khd->hqk", qb, k, quant) / np.sqrt(hd)
        qpos = i * blk + jnp.arange(blk)
        s = jnp.where(qpos[None, :, None] >= kpos[None, None, :], s, -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, quant)

    out = jax.lax.map(block, jnp.arange(S // blk))
    return out.reshape(S, H * hd)


def blocked_loss(x, labels, logits):
    """Mean next-token cross-entropy of one sequence from its final hidden
    states ``x`` (S, D), in blocks of ``BLOCK`` positions; ``logits(xb)``
    gives a block's (blk, vocab) logits."""
    S = x.shape[0]
    blk = min(BLOCK, S)

    @jax.checkpoint
    def nll(xb, lb):
        z = logits(xb)
        gold = jnp.take_along_axis(z, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - gold)

    xs, ls = x.reshape(S // blk, blk, -1), labels.reshape(S // blk, blk)
    return jnp.sum(jax.lax.map(lambda a: nll(*a), (xs, ls))) / S


@partial(jax.jit, static_argnums=(0, 1, 2))
def _seq_grad(model, d, quant, params, tokens, labels):
    return jax.value_and_grad(partial(model.seq_loss, d, quant))(params, tokens, labels)


@partial(jax.jit, donate_argnums=(0, 1))
def _accumulate(loss_acc, grad_acc, loss, grad):
    return loss_acc + loss, jax.tree_util.tree_map(jnp.add, grad_acc, grad)


@partial(jax.jit, donate_argnums=(1,), static_argnums=(3, 4))
def _momentum(params, m, g_sum, rows, opt):
    momentum, weight_decay = opt
    return jax.tree_util.tree_map(
        lambda p, mm, g: momentum * mm + (g / rows + weight_decay * p), params, m, g_sum)


@partial(jax.jit, static_argnums=(5,))
def _mix(p, m, pulled, lr, w, quant):
    def leaf(a, mm, b):
        h = _store(a - lr * mm, quant)
        return _store(h + w * (b - h), quant)
    return jax.tree_util.tree_map(leaf, p, m, pulled)


@jax.jit
def leaf_norms(tree):
    """Frobenius norm of every leaf, in ``tree_leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def grad(model, d, params, tokens, labels, quant=None):
    """(mean loss, mean gradient) of ``model`` over the rows of one worker's
    batch."""
    loss_acc, grad_acc = None, None
    for b in range(tokens.shape[0]):
        with jax.default_matmul_precision("highest"):
            loss, g = _seq_grad(model, d, quant, params, tokens[b], labels[b])
        if grad_acc is None:
            loss_acc, grad_acc = loss, g
        else:
            loss_acc, grad_acc = _accumulate(loss_acc, grad_acc, loss, g)
    return loss_acc / tokens.shape[0], grad_acc


def rounds(model, d, p0, batches, draws, *, lr, momentum, weight_decay,
           devices, quant=None, rows=None):
    """Replay NetMax rounds of ``model`` (a module of ``models/``, with its
    ``Dims`` ``d``) from the weights ``p0`` (one worker's tree).

    ``batches[r]``: (M, B, S + 1) token rows of round r; ``draws[r]``:
    (neighbours (M,), weights (M,)).  Worker i lives on
    ``devices[i % len(devices)]``.  ``rows`` (default: all) is how many rows
    of each batch the gradient takes.  Returns per round the mean loss, the
    per-worker leaf norms of the momentum after round 1 (the first gradient
    as the optimizer takes it, weight decay included), the per-worker leaf
    norms of the first gradient alone, and the per-worker leaf norms of the
    change of the weights over all rounds.
    """
    M = batches[0].shape[0]
    dev = [devices[i % len(devices)] for i in range(M)]
    to32 = lambda t, i: jax.device_put(
        jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), t), dev[i])
    p = [to32(p0, i) for i in range(M)]
    m = [jax.tree_util.tree_map(jnp.zeros_like, pi) for pi in p]
    losses, mom1, grad1 = [], None, None
    for r, (batch, (nb, w)) in enumerate(zip(batches, draws)):
        loss = 0.0
        for i in range(M):
            toks = batch[i] if rows is None else batch[i][:rows]
            toks = jax.device_put(toks, dev[i])
            li, g = grad(model, d, p[i], toks[:, :-1], toks[:, 1:], quant)
            loss += float(li) / M
            if r == 0:
                grad1 = (grad1 or []) + [np.asarray(leaf_norms(g)) / toks.shape[0]]
            m[i] = _momentum(p[i], m[i], g, float(toks.shape[0]),
                             (momentum, weight_decay))
            del g
        if r == 0:
            mom1 = [np.asarray(leaf_norms(mi)) for mi in m]
        new = [_mix(p[i], m[i], jax.device_put(p[int(nb[i])], dev[i]),
                    jnp.float32(lr), jnp.float32(w[i]), quant) for i in range(M)]
        p = new
        losses.append(loss)
    change = [np.asarray(leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p[i], to32(p0, i)))) for i in range(M)]
    return {"loss": losses, "mom1": np.stack(mom1), "grad1": np.stack(grad1),
            "change": np.stack(change)}
