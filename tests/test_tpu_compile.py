"""Compiles for a described TPU v5e (no chip attached): the kernels of the
main path at qwen1.5-0.5b widths, the chip smoke's whole training round and
the benchmark cell's (``qwen15-m2-short512``).

The chip's compiler refuses what interpret mode accepts: tiles that break the
(8, 128) rule, programs that do not fit HBM.  The topology is described only
inside the fixture, so every xdist worker collects the same tests and only the
worker given this file loads the TPU library.
"""

import os
import re
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs.base import get_arch
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gossip_mix import gossip_mix, gossip_mix_rows
from repro.launch.mesh import make_worker_mesh
from repro.launch.train import make_step
from repro.train.trainer import abstract_stacked

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

V5E_HBM_BYTES = 15.75 * 2**30  # what the v5e compiler reports as usable
QWEN = get_arch("qwen1.5-0.5b")
# The benchmark cell: f32 at 16 layers, M=2, 2 x 512 tokens a worker.  Its
# round undonated needs 13.60 GiB and recomputes 14 instructions.
CELL = replace(QWEN, dtype="float32", n_layers=16)
CELL_BATCH, CELL_SEQ = 2, 512
CELL_UNDONATED_BYTES = 13.60 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Entries compiled for a described chip cannot be read back without one.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hbm_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def _state_bytes(params, opt_state) -> int:
    return sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves((params, opt_state)))


def _remats(compiled) -> list[str]:
    """Instructions XLA's rematerialization pass recomputes (``.remat<n>``)."""
    return sorted(set(re.findall(r"[\w.\-]+\.remat\d*\b", compiled.as_text())))


def _round_args(cfg, M, opt, state_sh, repl_sh,
                per_worker=chip_smoke.BATCH_PER_WORKER, seq=chip_smoke.SEQ):
    params, opt_state = abstract_stacked(cfg, opt, M)
    put = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda l: _sds(l.shape, l.dtype, state_sh), t)
    shape = (M, per_worker, seq)
    batch = {k: _sds(shape, jnp.int32, state_sh) for k in ("tokens", "labels")}
    gi = {"neighbors": _sds((M,), jnp.int32, repl_sh),
          "weights": _sds((M,), jnp.float32, repl_sh),
          "lr": _sds((), jnp.float32, repl_sh)}
    return put(params), put(opt_state), batch, gi


@pytest.mark.parametrize("shape", [(QWEN.vocab_size, QWEN.d_model),
                                   (QWEN.d_model, QWEN.d_ff)])
def test_gossip_mix_compiles(one_chip, shape):
    x = _sds(shape, jnp.bfloat16, one_chip)
    w = _sds((), jnp.float32, one_chip)
    compiled = gossip_mix.lower(x, x, x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    q = _sds((1, 4096, QWEN.n_heads, QWEN.hd), jnp.bfloat16, one_chip)
    k = _sds((1, 4096, QWEN.n_kv_heads, QWEN.hd), jnp.bfloat16, one_chip)
    compiled = flash_attention.lower(q, k, k, causal=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("M", [2, 3, 8])
def test_gossip_mix_rows_compiles(one_chip, M, dtype):
    x = _sds((M, QWEN.d_model, QWEN.d_ff), dtype, one_chip)
    w = _sds((M,), jnp.float32, one_chip)
    compiled = gossip_mix_rows.lower(x, x, x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def rounds(one_chip):
    """name -> (compiled round, its params + optimizer-state bytes): the
    one-chip smoke's (unreduced qwen1.5-0.5b, M=2, gather pull) and the
    benchmark cell's."""
    out = {}
    for name, cfg, kw in [("smoke", QWEN, {}),
                          ("cell", CELL, dict(per_worker=CELL_BATCH, seq=CELL_SEQ))]:
        opt, step = make_step(cfg, 2)
        args = _round_args(cfg, 2, opt, one_chip, one_chip, **kw)
        out[name] = step.lower(*args).compile(), _state_bytes(*args[:2])
    return out


def test_smoke_round_fits_one_chip(rounds):
    compiled, _ = rounds["smoke"]
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("name", ["smoke", "cell"])
def test_round_writes_the_state_in_place(rounds, name):
    """The round donates params and optimizer state: every byte of them is
    an output aliased to its input."""
    compiled, state = rounds[name]
    assert compiled.memory_analysis().alias_size_in_bytes == state


@pytest.mark.parametrize("name", ["smoke", "cell"])
def test_round_recomputes_nothing(rounds, name):
    assert _remats(rounds[name][0]) == []


def test_cell_round_needs_less_than_undonated(rounds):
    compiled, _ = rounds["cell"]
    assert _hbm_bytes(compiled) < CELL_UNDONATED_BYTES


def test_four_chip_round_fits_and_gathers(topo):
    """The four-chip smoke's round: M=4 sharded one replica per chip."""
    cfg = replace(QWEN, n_layers=chip_smoke.FOUR_CHIP_LAYERS)
    mesh = make_worker_mesh(list(topo.devices))
    opt, step = make_step(cfg, 4, mesh=mesh)
    args = _round_args(cfg, 4, opt, NamedSharding(mesh, PartitionSpec("data")),
                       NamedSharding(mesh, PartitionSpec()))
    compiled = step.lower(*args).compile()
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES
    assert "all-gather" in compiled.as_text()
    # each chip writes its replica's state in place (a one-replica shard's
    # small leaves are padded on the device, so the alias reads a little more)
    assert compiled.memory_analysis().alias_size_in_bytes >= _state_bytes(*args[:2]) // 4
