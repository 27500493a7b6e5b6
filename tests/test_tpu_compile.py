"""The main-path Pallas kernels and the encoder compile for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology and refuses what Mosaic or XLA would refuse on the chip (layouts,
casts, ops with no lowering, memory).  Shapes are the farm's (B = 8 bins of
L = 128 lanes, S slots, R reads) and the ``sbert-paper`` encoder's published
widths.  Each kernel test asserts that the compiled program holds the Pallas
kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.embeddings.serving import _embed_batch
from repro.kernels.cobi_dynamics import (
    cobi_fused_best_batched_pallas,
    cobi_trajectory_batched_pallas,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ising_energy import ising_energy_batched_pallas
from repro.kernels.mcmc_dynamics import (
    mcmc_fused_best_batched_pallas,
    mcmc_sweep_batched_pallas,
)
from repro.models import init_params

B, L = 8, 128  # farm bins per launch, lanes per bin
COBI = dict(steps=300, dt=0.35, ks_max=1.2)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device cannot be read back from the
    # persistent cache without the chip; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _f32(*shape):
    return shape, jnp.float32


@pytest.mark.parametrize("reads", [8, 64])
@pytest.mark.parametrize("slots", [8, 16])
def test_cobi_fused_best_batched_compiles(one_chip, slots, reads):
    fn = functools.partial(cobi_fused_best_batched_pallas, replica_block=reads,
                           **COBI)
    text = _compiled_text(
        fn, one_chip, _f32(B, L, L), _f32(B, 1, L), _f32(B, L, L),
        _f32(B, 1, L), _f32(B, L, slots), _f32(B, 1, slots), _f32(B, reads, L),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("reads", [8, 64])
def test_cobi_trajectory_batched_compiles(one_chip, reads):
    fn = functools.partial(cobi_trajectory_batched_pallas, replica_block=reads,
                           **COBI)
    text = _compiled_text(fn, one_chip, _f32(B, L, L), _f32(B, 1, L),
                          _f32(B, reads, L))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("reads", [8, 64])
def test_ising_energy_batched_compiles(one_chip, reads):
    fn = functools.partial(ising_energy_batched_pallas, replica_block=reads)
    text = _compiled_text(fn, one_chip, _f32(B, reads, L), _f32(B, 1, L),
                          _f32(B, L, L))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("reads", [8, 64])
@pytest.mark.parametrize("mode", ["sweep", "random"])
@pytest.mark.parametrize(
    "kernel", [mcmc_fused_best_batched_pallas, mcmc_sweep_batched_pallas],
    ids=["fused_best", "sweep"],
)
def test_mcmc_kernels_compile(one_chip, kernel, mode, reads):
    fn = functools.partial(kernel, sweeps=37, mode=mode, replica_block=reads)
    text = _compiled_text(fn, one_chip, _f32(B, L, L), _f32(B, 1, L),
                          _f32(B, reads, L), ((B, 1, L), jnp.uint32),
                          _f32(B, 1, L))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_at_encoder_head_dim(one_chip):
    """The opt-in encoder flash path: sbert-paper's 12 heads of 64 dims over
    2048 positions, bf16."""
    qkv = ((4, 2048, 12, 64), jnp.bfloat16)
    fn = functools.partial(flash_attention, causal=True)
    text = _compiled_text(fn, one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_encoder_compiles_at_published_widths(one_chip):
    """The served encoder launch at 12 x 768, d_ff 3072, batch 4 x 2048
    tokens, with SDPA attention; it must fit one v5e's 16 GB."""
    cfg = get_config("sbert-paper")
    abstract = jax.eval_shape(functools.partial(init_params, cfg),
                              jax.random.key(0))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        abstract)
    ids = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=one_chip)
    compiled = (jax.jit(_embed_batch, static_argnums=(0, 4))
                .lower(cfg, params, ids, ids, 64).compile())
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16 * 2**30, used
