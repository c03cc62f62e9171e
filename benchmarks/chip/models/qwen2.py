"""A Qwen2-family decoder (``model_type`` ``qwen2``): its sizes, weights,
plain float32 forward pass and model FLOPs, for the chip benchmark.

Written from the published Qwen2 model: RMSNorm, rotary embeddings with
``rotate_half``, grouped-query attention with q/k/v biases, a SwiGLU MLP
and tied embeddings.  It imports nothing of the program.  Every matrix is
drawn from N(0, initializer_range), biases are zero and norm scales one, as
the published ``config.json`` and its model code initialise them; the tree
has the layout the program trains (layers stacked on a leading axis).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from reference import attention, blocked_loss, mm, rms, rope


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rms_eps: float
    init_std: float
    qkv_bias: bool

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        """From a configuration file's published ``config.json`` keys."""
        if not c["tie_word_embeddings"] or c["hidden_act"] != "silu":
            raise ValueError("only tied-embedding SwiGLU decoders are described")
        return cls(
            layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
            head_dim=c["hidden_size"] // c["num_attention_heads"],
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]), rms_eps=float(c["rms_norm_eps"]),
            init_std=float(c["initializer_range"]), qkv_bias=bool(c["qkv_bias"]),
        )


def program_keys(d: Dims) -> dict:
    """The program's ``ArchConfig`` fields that have to equal these."""
    return dict(n_layers=d.layers, d_model=d.d_model, n_heads=d.heads,
                n_kv_heads=d.kv_heads, hd=d.head_dim, d_ff=d.d_ff,
                vocab_size=d.vocab, rope_theta=d.rope_theta,
                qkv_bias=d.qkv_bias, tie_embeddings=True, family="dense",
                n_vis_tokens=0, norm="rmsnorm", activation="swiglu",
                pad_heads=0, pad_kv_heads=0)


def shapes(d: Dims) -> dict:
    """Leaf shapes, in the program's layout."""
    L, D, F = d.layers, d.d_model, d.d_ff
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    attn = {"wq": (L, D, q), "wk": (L, D, kv), "wv": (L, D, kv), "wo": (L, q, D)}
    if d.qkv_bias:
        attn.update(bq=(L, q), bk=(L, kv), bv=(L, kv))
    return {
        "embed": {"table": (d.vocab, D)},
        "blocks": {
            "ln1": {"scale": (L, D)},
            "attn": attn,
            "ln2": {"scale": (L, D)},
            "mlp": {"w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D)},
        },
        "final_norm": {"scale": (D,)},
    }


def init(d: Dims, key, dtype) -> dict:
    """Weights from ``key`` in ``dtype``; call it under ``jax.jit``."""
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes(d), is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        if name == "scale":
            leaf = jnp.ones(shape, dtype)
        elif name.startswith("b"):
            leaf = jnp.zeros(shape, dtype)
        else:
            draw = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            leaf = (draw * d.init_std).astype(dtype)
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(tree, leaves)


def _layer(d: Dims, quant, x, lp):
    S = x.shape[0]
    a = lp["attn"]
    h = rms(x, lp["ln1"]["scale"], d.rms_eps)
    q, k, v = (mm("sd,de->se", h, a[w], quant) for w in ("wq", "wk", "wv"))
    if d.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q.reshape(S, d.heads, d.head_dim), d.rope_theta)
    k = rope(k.reshape(S, d.kv_heads, d.head_dim), d.rope_theta)
    v = v.reshape(S, d.kv_heads, d.head_dim)
    x = x + mm("se,ed->sd", attention(q, k, v, quant), a["wo"], quant)
    m = lp["mlp"]
    h = rms(x, lp["ln2"]["scale"], d.rms_eps)
    g = jax.nn.silu(mm("sd,df->sf", h, m["w_gate"], quant))
    u = mm("sd,df->sf", h, m["w_up"], quant)
    return x + mm("sf,fd->sd", g * u, m["w_down"], quant), None


def seq_loss(d: Dims, quant, params, tokens, labels):
    """Mean next-token cross-entropy of one sequence (tokens, labels: (S,))."""
    table = params["embed"]["table"]
    x = table[tokens]
    x, _ = jax.lax.scan(jax.checkpoint(partial(_layer, d, quant)), x, params["blocks"])
    x = rms(x, params["final_norm"]["scale"], d.rms_eps)
    return blocked_loss(x, labels, lambda xb: mm("sd,vd->sv", xb, table, quant))


def forward_flops_per_sequence(d: Dims, seq: int) -> int:
    """The matrix products of one sequence's forward pass, the tied output
    head among them, and of causal attention only the unmasked query-key
    pairs."""
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    layer_weights = d.d_model * (2 * q + 2 * kv) + 3 * d.d_model * d.d_ff
    matmuls = 2 * seq * (d.layers * layer_weights + d.d_model * d.vocab)
    pairs = seq * (seq + 1) // 2  # causal query-key pairs
    scores = d.layers * 2 * 2 * pairs * q  # scores and values
    return matmuls + scores
