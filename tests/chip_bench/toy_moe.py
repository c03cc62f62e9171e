"""A toy mixture-of-experts decoder (``model_type`` ``toy_moe``), the model
module of a configuration that the benchmark does not ship.  The tests copy
it into a copy of the benchmark's directory as ``models/toy_moe.py``, to
show that a model is added as files alone.

Every layer is grouped-query attention without biases and a routed SwiGLU
MLP: a float32 router, softmax scores, the top ``num_experts_per_tok``
experts with their scores renormalised to sum to one, and a Switch-style
balance loss ``E * sum_e mean_prob_e * routed_share_e`` per layer, added to
the cross-entropy with ``router_aux_loss_coef``; the output head is untied.
The balance loss is taken per sequence, so the program's microbatch is one
sequence; ``num_experts_per_tok * capacity_factor >= num_local_experts``,
so the program drops no token.
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
    experts: int
    top_k: int
    capacity_factor: float
    aux_coef: float
    rope_theta: float
    rms_eps: float
    init_std: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        if c["tie_word_embeddings"]:
            raise ValueError("toy_moe has an untied head")
        return cls(
            layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
            head_dim=c["head_dim"], d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            experts=c["num_local_experts"], top_k=c["num_experts_per_tok"],
            capacity_factor=float(c["capacity_factor"]),
            aux_coef=float(c["router_aux_loss_coef"]),
            rope_theta=float(c["rope_theta"]), rms_eps=float(c["rms_norm_eps"]),
            init_std=float(c["initializer_range"]),
        )


def program_keys(d: Dims) -> dict:
    return {"n_layers": d.layers, "d_model": d.d_model, "n_heads": d.heads,
            "n_kv_heads": d.kv_heads, "hd": d.head_dim, "d_ff": d.d_ff,
            "vocab_size": d.vocab, "rope_theta": d.rope_theta, "qkv_bias": False,
            "tie_embeddings": False, "family": "moe", "n_vis_tokens": 0,
            "norm": "rmsnorm", "activation": "swiglu", "pad_heads": 0,
            "pad_kv_heads": 0, "moe.n_experts": d.experts, "moe.top_k": d.top_k,
            "moe.capacity_factor": d.capacity_factor, "moe.layout": "all"}


def shapes(d: Dims) -> dict:
    L, D, F, E = d.layers, d.d_model, d.d_ff, d.experts
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    return {
        "embed": {"table": (d.vocab, D)},
        "blocks": {
            "ln1": {"scale": (L, D)},
            "attn": {"wq": (L, D, q), "wk": (L, D, kv), "wv": (L, D, kv), "wo": (L, q, D)},
            "ln2": {"scale": (L, D)},
            "moe": {"w_router": (L, D, E), "w_gate": (L, E, D, F), "w_up": (L, E, D, F),
                    "w_down": (L, E, F, D)},
        },
        "final_norm": {"scale": (D,)},
        "lm_head": {"w": (D, d.vocab)},
    }


def init(d: Dims, key, dtype) -> dict:
    """N(0, initializer_range) matrices, unit norm scales; the router is
    float32 whatever ``dtype``, as the program keeps it."""
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes(d), is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        if name == "scale":
            leaves.append(jnp.ones(shape, dtype))
            continue
        draw = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * d.init_std
        leaves.append(draw if name == "w_router" else draw.astype(dtype))
    return jax.tree_util.tree_unflatten(tree, leaves)


def _layer(d: Dims, quant, carry, lp):
    x, aux = carry
    S = x.shape[0]
    a = lp["attn"]
    h = rms(x, lp["ln1"]["scale"], d.rms_eps)
    q, k, v = (mm("sd,de->se", h, a[w], quant) for w in ("wq", "wk", "wv"))
    q = rope(q.reshape(S, d.heads, d.head_dim), d.rope_theta)
    k = rope(k.reshape(S, d.kv_heads, d.head_dim), d.rope_theta)
    v = v.reshape(S, d.kv_heads, d.head_dim)
    x = x + mm("se,ed->sd", attention(q, k, v, quant), a["wo"], quant)

    m = lp["moe"]
    h = rms(x, lp["ln2"]["scale"], d.rms_eps)
    probs = jax.nn.softmax(mm("sd,de->se", h, m["w_router"], quant), axis=-1)
    top, idx = jax.lax.top_k(probs, d.top_k)
    picked = jax.nn.one_hot(idx, d.experts)  # (S, K, E)
    gates = jnp.sum(picked * (top / jnp.sum(top, -1, keepdims=True))[..., None], axis=1)
    g = jax.nn.silu(mm("sd,edf->sef", h, m["w_gate"], quant))
    u = mm("sd,edf->sef", h, m["w_up"], quant)
    y = mm("sef,efd->sed", g * u, m["w_down"], quant)  # every expert, every token
    x = x + jnp.sum(gates[..., None] * y, axis=1)
    share = jnp.mean(jnp.sum(picked, axis=1), axis=0) / d.top_k
    aux = aux + d.experts * jnp.sum(jnp.mean(probs, axis=0) * share)
    return (x, aux), None


def seq_loss(d: Dims, quant, params, tokens, labels):
    x = params["embed"]["table"][tokens]
    (x, aux), _ = jax.lax.scan(jax.checkpoint(partial(_layer, d, quant)),
                               (x, jnp.zeros((), jnp.float32)), params["blocks"])
    x = rms(x, params["final_norm"]["scale"], d.rms_eps)
    head = params["lm_head"]["w"]
    return (blocked_loss(x, labels, lambda xb: mm("sd,dv->sv", xb, head, quant))
            + d.aux_coef * aux)


def forward_flops_per_sequence(d: Dims, seq: int) -> int:
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    per_token = (d.layers * (d.d_model * (2 * q + 2 * kv + d.experts)
                             + d.top_k * 3 * d.d_model * d.d_ff)
                 + d.d_model * d.vocab)
    pairs = seq * (seq + 1) // 2
    return 2 * seq * per_token + d.layers * 2 * 2 * pairs * q
