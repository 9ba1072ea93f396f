"""Mamba2 SSD chunked scan, Pallas/TPU.

Grid = (B, n_head_tiles, n_chunks) with chunks innermost; the running
inter-chunk state (Ht, P, N) lives in VMEM scratch and carries across
chunk iterations — the TPU-native version of the paper's "keep the
recurrent state close to the compute" (the SoC analogue holds its own
working set; cf. DESIGN.md path mapping).

Layouts are chosen so every block's last two dims are TPU tiles (a
multiple of (8, 128) or the whole array dim): x/y go head-major
(B, H, S, P), dt goes (B, n_chunks, H, L) so a chunk's step sizes are
one (Ht, L) tile, and A is a (H, 1) column. The head tile Ht is a
multiple of 8 dividing H, or all of H.

Per chunk and head the kernel computes, entirely in VMEM and with 2-D
matmuls only:
  cum    = dA @ triu(1)                   (in-chunk cumsum, on the MXU)
  intra  = (tril(C B^T * decay) * dt) @ x (the quadratic branch)
  inter  = C h_prev^T * exp(cum)          (read of the carried state)
  h_new  = h_prev * exp(sum_dA) + (x * w)^T B,  w = exp(last - cum) dt

VMEM per step (L=chunk, Ht=head tile, P=head dim, N=state): x and y
(Ht, L, P) + one (L, L) score tile + state (Ht, P, N), all f32 — e.g.
L=256, Ht=8, P=64, N=128: ~1.6 MB, plus double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_ref):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    dt = dt_ref[0, 0].astype(jnp.float32)    # (Ht, L)
    A = a_ref[...].astype(jnp.float32)       # (Ht, 1)
    Bm = b_ref[0].astype(jnp.float32)        # (L, N)
    C = c_ref[0].astype(jnp.float32)         # (L, N)
    ht, L = dt.shape

    ti = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tril = si <= ti                          # [t, s]: s at or before t
    dA = dt * A                              # (Ht, L)
    # in-chunk cumsum, and the (L, Ht) column copies, as 2-D matmuls
    cum = _dot(dA, (ti <= si).astype(jnp.float32), ((1,), (0,)))      # (Ht, L)
    cum_t = _dot(tril.astype(jnp.float32), dA, ((1,), (1,)))          # (L, Ht)
    dt_t = _dot(jnp.eye(L, dtype=jnp.float32), dt, ((1,), (1,)))      # (L, Ht)
    CB = _dot(C, Bm, ((1,), (1,)))                                    # (L, L)

    for h in range(ht):
        x = x_ref[0, h].astype(jnp.float32)  # (L, P)
        row = cum[h:h + 1, :]                # (1, L)
        col = cum_t[:, h:h + 1]              # (L, 1)
        last = row[:, L - 1:]                # (1, 1)
        # ---- intra-chunk ----
        decay = jnp.exp(jnp.where(tril, col - row, -jnp.inf))
        scores = CB * decay * dt[h:h + 1, :]
        y = _dot(scores, x, ((1,), (0,)))                             # (L, P)
        # ---- inter-chunk: read carried state ----
        h_prev = h_ref[h]                                             # (P, N)
        y += _dot(C, h_prev, ((1,), (1,))) * jnp.exp(col)
        # ---- state update ----
        w = jnp.exp(last - col) * dt_t[:, h:h + 1]                    # (L, 1)
        h_ref[h] = (h_prev * jnp.exp(jnp.sum(last))
                    + _dot(x * w, Bm, ((0,), (0,))))
        y_ref[0, h] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _fin():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def _head_tile(h: int, head_tile: int) -> int:
    """Largest multiple of 8 that divides ``h`` and is at most
    ``head_tile``; all of ``h`` when there is none (a tile of the
    (Ht, L) dt block must be a multiple of 8 or the whole dim)."""
    for ht in range(head_tile - head_tile % 8, 0, -8):
        if h % ht == 0:
            return ht
    return h


def ssd_scan_pallas(x: jax.Array, dt: jax.Array, A: jax.Array,
                    Bm: jax.Array, C: jax.Array, *,
                    chunk: int = 128, head_tile: int = 8,
                    interpret: bool = False):
    """x (B,S,H,P); dt (B,S,H); A (H,); Bm/C (B,S,N).
    Returns (y (B,S,H,P) f32, final state (B,H,P,N) f32)."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    ht = _head_tile(h, head_tile)
    nc, nh = s // chunk, h // ht

    xt = x.swapaxes(1, 2)                                        # (B,H,S,P)
    dtt = dt.reshape(b, nc, chunk, h).swapaxes(2, 3)             # (B,nc,H,L)
    y, hfin = pl.pallas_call(
        _ssd_kernel,
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec((1, ht, chunk, p), lambda b_, hi, ci: (b_, hi, ci, 0)),
            pl.BlockSpec((1, 1, ht, chunk), lambda b_, hi, ci: (b_, ci, hi, 0)),
            pl.BlockSpec((ht, 1), lambda b_, hi, ci: (hi, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, hi, ci: (b_, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, hi, ci: (b_, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, ht, chunk, p), lambda b_, hi, ci: (b_, hi, ci, 0)),
            pl.BlockSpec((1, ht, p, n), lambda b_, hi, ci: (b_, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((ht, p, n), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, A[:, None], Bm, C)
    return y.swapaxes(1, 2), hfin
