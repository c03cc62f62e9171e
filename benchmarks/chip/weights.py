"""The benchmark's own weights for a Qwen2-family decoder, from the seed.

Every matrix is drawn from N(0, initializer_range), biases are zero and norm
scales one, as the published ``config.json`` and its model code initialise
them.  The tree has the layout the program trains (layers stacked on a
leading axis); the harness hands it to the program in place of the program's
own initialiser, and the reference starts from the same values.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


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
