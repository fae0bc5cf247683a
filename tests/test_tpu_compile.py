"""Native compiles for one TPU v5e chip, described and not attached.

The TPU compiler ships with jaxlib, so these tests compile the serving
path's Pallas kernels and one whole decode step at qwen3-4b's full widths
without a chip: what the chip's compiler would refuse (misaligned blocks,
too much VMEM, a step that does not fit HBM) fails here. Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this file.
Kernel mode follows ``jax.default_backend()``, which is the CPU here, so
each test forces native mode itself (``native`` fixture).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops as kernel_ops
from repro.models import transformer as T
from repro.serve import engine as engine_mod

CFG = configs.get_config("qwen3-4b")
PAGE = 128
MAX_LEN = 2048
BATCH = 8
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def native(monkeypatch):
    """Compile the kernels as the chip would (not interpret mode), with
    the persistent compilation cache off: an entry compiled for a
    described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(kernel_ops, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _pool(one_chip, batch):
    n_pages = 1 + batch * MAX_LEN // PAGE
    pool = _spec((n_pages, PAGE, CFG.n_kv_heads, CFG.dhead), CFG.dtype,
                 one_chip)
    table = _spec((batch, MAX_LEN // PAGE), np.int32, one_chip)
    return pool, table


def test_flash_decode_paged_compiles_native(one_chip, native):
    pool, table = _pool(one_chip, BATCH)
    q = _spec((BATCH, CFG.n_heads, CFG.dhead), CFG.dtype, one_chip)
    lengths = _spec((BATCH,), np.int32, one_chip)
    text = jax.jit(kernel_ops.flash_decode_paged).lower(
        q, pool, pool, table, lengths).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "batch,n_pages,max_len,kvh,d",
    [(32, 192, 4096, 8, 128), (5, 81, 2048, 32, 96)],
    ids=["qwen3-4b", "phi3-mini"])
def test_flash_decode_paged_compiles_at_cell_sizes(one_chip, native, batch,
                                                   n_pages, max_len, kvh, d):
    """Both benchmark cells' decode shapes (pages of 128 rows, 32 query
    heads). phi3's 96-lane head dim pads to 128 in VMEM, so each of its
    double-buffered K and V pages takes 1 MiB there."""
    pool = _spec((n_pages, PAGE, kvh, d), jnp.bfloat16, one_chip)
    q = _spec((batch, 32, d), jnp.bfloat16, one_chip)
    table = _spec((batch, max_len // PAGE), np.int32, one_chip)
    lengths = _spec((batch,), np.int32, one_chip)
    text = jax.jit(kernel_ops.flash_decode_paged).lower(
        q, pool, pool, table, lengths).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_attention_paged_compiles_native(one_chip, native):
    pool, table = _pool(one_chip, 1)
    q = _spec((1, 256, CFG.n_heads, CFG.dhead), CFG.dtype, one_chip)
    starts = _spec((1,), np.int32, one_chip)
    text = jax.jit(kernel_ops.flash_attention_paged).lower(
        q, pool, pool, table, starts).compile().as_text()
    assert "tpu_custom_call" in text


def test_full_width_decode_step_fits_one_chip(one_chip, native):
    """The engine's decode step at full width, with weights made as the
    serving launcher makes them (compute dtype), fits 16 GiB of HBM. With
    float32 weights the same step needs about 27 GB and is refused."""
    cfg = dataclasses.replace(CFG, use_flash=True)
    key = jax.random.PRNGKey(0)
    put = lambda a: _spec(a.shape, a.dtype, one_chip)  # noqa: E731
    params = jax.tree.map(put, jax.eval_shape(
        lambda k: T.init_params(k, cfg, dtype=cfg.dtype), key))
    assert {a.dtype for a in jax.tree.leaves(params)} == {cfg.dtype}
    n_pages = 1 + BATCH * MAX_LEN // PAGE
    caches = jax.tree.map(put, jax.eval_shape(
        lambda: T.init_paged_caches(cfg, BATCH, MAX_LEN, PAGE, n_pages)))
    tokens = _spec((BATCH,), np.int32, one_chip)
    step = jax.jit(lambda p, t, c: engine_mod.decode_step(p, cfg, t, c),
                   donate_argnums=(2,))
    compiled = step.lower(params, tokens, caches).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used < HBM_BYTES, used / 2**30
