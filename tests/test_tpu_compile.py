"""Compile the served path's device programs for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed alongside JAX, compiles
for a chip that is described and not attached, and refuses what the chip
would refuse (block shapes that do not tile, SMEM/VMEM overflows) — which
interpret-mode kernel tests cannot see.  The topology is described inside a
fixture, never at import, so that only the worker running this file loads
the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.kernels.overlay_patch.kernel import overlay_patch_kernel, page_shape
from repro.models import blocks
from repro.models.layers import embed_specs
from repro.serve.instance import _embed_fn, _head_fn, _layer_fn
from repro.sharding.partition import abstract_from_specs

ARCHS = ("qwen1.5-0.5b", "mamba2-780m")  # the benchmark's configurations
PROMPT_LEN = 1024  # the benchmark's prompts
PAGE_BYTES = 64 * 1024  # the JIF default page


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent cache
    # but never read back here: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe it skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    return compiled.as_text()


@pytest.mark.parametrize(
    "n_pages,n_priv,dtype",
    [
        (9496, 4096, jnp.float32),  # the 151936 x 1024 f32 embedding
        (5, 3, jnp.bfloat16),
    ],
)
def test_overlay_patch_kernel_compiles(one_chip, n_pages, n_priv, dtype):
    page = page_shape(PAGE_BYTES // jnp.dtype(dtype).itemsize)
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    hlo = _compile(
        overlay_patch_kernel,
        spec((n_pages, *page), dtype), spec((n_priv, *page), dtype),
        spec((n_pages,), jnp.int32), spec((n_pages,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo  # the Pallas kernel, not a fallback


@pytest.fixture(scope="module", params=ARCHS)
def full_cfg(request) -> ModelConfig:
    return get_config(request.param)  # the published widths, not .reduced()


def _layer_shapes(cfg, one_chip):
    spec = cfg.pattern[0]
    params = _shapes(abstract_from_specs(blocks.layer_specs(cfg, spec),
                                         jnp.float32), one_chip)
    x = jax.ShapeDtypeStruct((1, PROMPT_LEN, cfg.d_model), jnp.float32,
                             sharding=one_chip)
    positions = jax.ShapeDtypeStruct((1, PROMPT_LEN), jnp.int32,
                                     sharding=one_chip)
    return spec, params, x, positions


def test_full_width_prefill_and_decode_layers_compile(full_cfg, one_chip):
    spec, params, x, positions = _layer_shapes(full_cfg, one_chip)
    prefill = _layer_fn(full_cfg, spec, "prefill")
    assert _compile(prefill, params, x, positions, None, None)
    _, cache = jax.eval_shape(prefill, params, x, positions, None, None)
    x1 = jax.ShapeDtypeStruct((1, 1, full_cfg.d_model), jnp.float32,
                              sharding=one_chip)
    pos1 = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    decode = _layer_fn(full_cfg, spec, "decode")
    assert _compile(decode, params, x1, pos1, _shapes(cache, one_chip), pos)


def test_full_width_embed_and_head_compile(full_cfg, one_chip):
    p_embed = _shapes(abstract_from_specs(embed_specs(full_cfg), jnp.float32),
                      one_chip)
    toks = jax.ShapeDtypeStruct((1, PROMPT_LEN), jnp.int32, sharding=one_chip)
    assert _compile(_embed_fn(full_cfg), p_embed, toks)
    norm = jax.ShapeDtypeStruct((full_cfg.d_model,), jnp.float32,
                                sharding=one_chip)
    x = jax.ShapeDtypeStruct((1, PROMPT_LEN, full_cfg.d_model), jnp.float32,
                             sharding=one_chip)
    assert _compile(_head_fn(full_cfg), p_embed, norm, x)
