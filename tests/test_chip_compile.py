"""Compile guard for the TPU v5e, with no chip attached.

Interpret-mode kernel tests cannot see what the chip's compiler refuses:
block shapes off the (8, 128) tiling, too much VMEM, or a program that
does not fit HBM. The TPU compiler is installed here and compiles for a
described ``v5e:2x2``. These tests compile the main-path kernels at real
widths with ``interpret=False`` and the internlm2-1.8b serving programs
at the chip smoke's cache size, and check the compiler's own output.

The topology is described only inside the ``topo`` fixture: libtpu may
be loaded by one process at a time, so nothing here touches it while
the module is imported or collected.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402  (SLOTS / MAX_LEN / PROMPT_LEN of the chip run)
from repro.configs import get_config  # noqa: E402
from repro.kernels.decode_attention import kernel as DK  # noqa: E402
from repro.kernels.flash_attention import kernel as FK  # noqa: E402
from repro.kernels.ssd_scan import kernel as SK  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.params import abstract_params  # noqa: E402

HBM_BYTES = 15.75e9      # what XLA:TPU lets one v5e program use


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described device is written to it but cannot be read
    back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _abstract(tree, sharding):
    return jax.tree.map(lambda s: _sds(sharding, s.shape, s.dtype), tree)


def _mosaic(fn, *args) -> bool:
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles(one_chip):
    cfg = get_config("internlm2-1.8b")
    s, hd = chip_smoke.KERNEL_SEQ, cfg.head_dim
    q = _sds(one_chip, (1, cfg.num_heads, s, hd), jnp.bfloat16)
    kv = _sds(one_chip, (1, cfg.num_kv_heads, s, hd), jnp.bfloat16)
    assert _mosaic(lambda q, k, v: FK.flash_attention_bhsd(
        q, k, v, causal=True, interpret=False), q, kv, kv)


@pytest.mark.parametrize("batch,seq,dtype", [
    (chip_smoke.SLOTS, chip_smoke.MAX_LEN, jnp.bfloat16),
    (8, 2048, jnp.bfloat16),
    (8, 2048, jnp.float32),
])
def test_decode_attention_compiles(one_chip, batch, seq, dtype):
    cfg = get_config("internlm2-1.8b")
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    q = _sds(one_chip, (batch, hkv, cfg.num_heads // hkv, hd), dtype)
    cache = _sds(one_chip, (batch, hkv, seq, hd), dtype)
    clen = _sds(one_chip, (), jnp.int32)
    assert _mosaic(lambda q, k, v, n: DK.decode_attention_bhgd(
        q, k, v, n, interpret=False), q, cache, cache, clen)


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-2.7b")
    h, p, n, s = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, 2048
    assert h == 80 and cfg.ssm_chunk == 256
    args = (_sds(one_chip, (1, s, h, p)), _sds(one_chip, (1, s, h)),
            _sds(one_chip, (h,)), _sds(one_chip, (1, s, n)),
            _sds(one_chip, (1, s, n)))
    assert _mosaic(lambda *a: SK.ssd_scan_pallas(
        *a, chunk=cfg.ssm_chunk, interpret=False), *args)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_internlm2_serving_program_fits_hbm(one_chip, program):
    """The engine's decode step at the smoke's slots x max_len (f32
    cache, the engine default) and its prefill bucket, as compiled for
    one v5e: arguments + temporaries + outputs within HBM."""
    cfg = get_config("internlm2-1.8b")
    slots, max_len = chip_smoke.SLOTS, chip_smoke.MAX_LEN
    params = _abstract(abstract_params(cfg)[0], one_chip)
    if program == "decode":
        cache = _abstract(jax.eval_shape(
            lambda: M.init_cache(cfg, slots, max_len, jnp.float32)[0]), one_chip)
        lowered = jax.jit(lambda p, t, c, pos: M.decode_step(
            cfg, p, t, c, pos)).lower(
            params, _sds(one_chip, (slots, 1), jnp.int32), cache,
            _sds(one_chip, (slots,), jnp.int32))
    else:
        lowered = jax.jit(lambda p, t, n: M.prefill(
            cfg, p, t, max_len, cache_dtype=jnp.float32, length=n)).lower(
            params, _sds(one_chip, (1, chip_smoke.PROMPT_LEN), jnp.int32),
            _sds(one_chip, (), jnp.int32))
    mem = lowered.compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    assert total <= HBM_BYTES, f"{program}: {total / 1e9:.2f} GB"
