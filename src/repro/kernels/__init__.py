"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel package ships:
  kernel.py — pl.pallas_call with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (``interpret()`` picks the mode)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

Kernels:
  flash_attention  — causal GQA attention w/ sliding window + logit softcap
  decode_attention — single-token flash-decoding against a KV cache
  ssd_scan         — Mamba2 SSD chunked scan (state carried across chunks)
  quant            — blockwise int8 compress/decompress (grad/ckpt/KV paths)
"""
import jax


def interpret() -> bool:
    """Interpret-mode switch for every kernel wrapper: True only on the
    CPU backend, which is where the tests run the kernel bodies. On a
    TPU the kernels always compile to Mosaic; a chip run never
    interprets."""
    return jax.default_backend() == "cpu"
