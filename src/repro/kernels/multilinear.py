"""Pallas TPU kernels for MULTILINEAR / MULTILINEAR-HM batched string hashing.

TPU mapping of the paper's inner loop (DESIGN.md §2):

- The VPU is 8x128 lanes of 32-bit ALUs -> all mod-2^64 math is (hi, lo)
  uint32 limb pairs (see repro.core.limbs).
- A grid cell processes a (block_b, block_n) tile of tokens against a
  (block_n,) tile of keys, both staged HBM->VMEM by BlockSpec; the key
  stream is the paper's "large buffer of random numbers" and is the reason
  this op is memory-bound on TPU (12 key bytes + 4 data bytes per char).
- Per-tile reduction uses the *digit trick*: sum_i (hi_i 2^32 + lo_i)
  mod 2^64 == ((sum hi_i mod 2^32) << 32) + sum(lo&0xFFFF) + sum(lo>>16)<<16
  where both 16-bit-digit sums are EXACT in uint32 for block_n <= 2^16.
  This keeps the reduction a pair of dense lane reductions (VPU-native)
  instead of a carry chain -- the TPU analogue of the paper's observation
  that evaluation *order* (2-by-2 unroll) is a hardware scheduling choice,
  not an algebraic one.
- Tiles along n accumulate into the same output block (revisited output,
  matmul-style); m1 and the final >>32 happen in the jit wrapper.

Alignment: callers (ops.py) zero-pad tokens AND keys to block multiples.
Zero keys make padded positions contribute exactly 0 in both families
((m+0)*(0+s')=0 needs m=0 too -- hence keys are padded, not just tokens).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..core import limbs

U32 = jnp.uint32
I32 = jnp.int32
MASK16 = np.uint32(0xFFFF)  # numpy scalar: literal, not a captured const

DEFAULT_BLOCK_B = 8
DEFAULT_BLOCK_N = 1024


def _digit_reduce_mod64(p_hi, p_lo, axis):
    """Exact sum_i (p_hi,p_lo) mod 2^64 over `axis` using 16-bit digit sums.

    Requires the reduced extent <= 2^16 (checked by callers via block_n).
    Returns (hi, lo) uint32 with the axis removed.

    The sums run in int32 through bitcasts (Mosaic reduces signed 32-bit
    integers only): two's-complement adds wrap mod 2^32 exactly like
    uint32 ones, and each digit sum is < 2^32, so reading the int32 sum
    back as uint32 is exact.
    """
    def _sum(x):
        s = jnp.sum(jax.lax.bitcast_convert_type(x, I32), axis=axis)
        return jax.lax.bitcast_convert_type(s, U32)

    hi_sum = _sum(p_hi)                                        # wraps mod 2^32: correct
    lo_low = _sum(p_lo & MASK16)                               # exact (< 2^32)
    lo_high = _sum(p_lo >> 16)                                 # exact (< 2^32)
    lo = lo_low + (lo_high << 16)                              # may wrap: track carry
    carry = (lo < lo_low).astype(U32)
    hi = hi_sum + (lo_high >> 16) + carry
    return hi, lo


def _accumulate_out(out_ref, part_hi, part_lo, first):
    """out_ref[..., 0]=hi, [..., 1]=lo; add64-accumulate across grid steps."""
    @pl.when(first)
    def _init():
        out_ref[:, 0] = part_hi
        out_ref[:, 1] = part_lo

    @pl.when(jnp.logical_not(first))
    def _acc():
        acc_hi, acc_lo = limbs.add64((out_ref[:, 0], out_ref[:, 1]), (part_hi, part_lo))
        out_ref[:, 0] = acc_hi
        out_ref[:, 1] = acc_lo


def _multilinear_kernel(tok_ref, kh_ref, kl_ref, out_ref):
    """One (block_b, block_n) tile: p = key64 * tok32; digit-reduce; accumulate."""
    toks = tok_ref[...]
    kh = kh_ref[...]
    kl = kl_ref[...]
    p_hi, p_lo = limbs.mul64_u32((kh[None, :], kl[None, :]), toks)
    part_hi, part_lo = _digit_reduce_mod64(p_hi, p_lo, axis=1)
    _accumulate_out(out_ref, part_hi, part_lo, pl.program_id(1) == 0)


def _multilinear_hm_kernel(tok_ref, kh_ref, kl_ref, out_ref):
    """HM tile: pair tokens/keys, (m+s)(m'+s') low-64 products, reduce.

    Pairing via reshape (bb, bn) -> (bb, bn//2, 2): lane-contiguous, no
    strided slices (Mosaic-friendly).
    """
    toks = tok_ref[...]
    bb, bn = toks.shape
    tp = toks.reshape(bb, bn // 2, 2)
    kh = kh_ref[...].reshape(bn // 2, 2)
    kl = kl_ref[...].reshape(bn // 2, 2)
    a = limbs.add64_u32((kh[None, :, 0], kl[None, :, 0]), tp[:, :, 0])
    b = limbs.add64_u32((kh[None, :, 1], kl[None, :, 1]), tp[:, :, 1])
    p_hi, p_lo = limbs.mul64_low(a, b)
    part_hi, part_lo = _digit_reduce_mod64(p_hi, p_lo, axis=1)
    _accumulate_out(out_ref, part_hi, part_lo, pl.program_id(1) == 0)


@functools.partial(
    jax.jit, static_argnames=("family", "block_b", "block_n", "interpret")
)
def hash_blocks(
    tokens,
    key_hi,
    key_lo,
    *,
    family: str = "multilinear",
    block_b: int = DEFAULT_BLOCK_B,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
):
    """Raw kernel entry: (B, N) uint32 tokens (B, N already block-aligned,
    keys WITHOUT m1 -- i.e. key_hi/lo[i] multiplies tokens[:, i]) ->
    (B, 2) uint32 accumulators (hi, lo) of sum_i m_i s_i mod 2^64.
    """
    B, N = tokens.shape
    assert B % block_b == 0 and N % block_n == 0, (B, N, block_b, block_n)
    assert block_n <= 1 << 16, "digit-trick exactness bound"
    assert block_n % 2 == 0
    kernel = _multilinear_kernel if family in ("multilinear", "multilinear_2x2") else _multilinear_hm_kernel
    grid = (B // block_b, N // block_n)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((block_n,), lambda i, j: (j,)),
            pl.BlockSpec((block_n,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((block_b, 2), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 2), U32),
        interpret=interpret,
    )(tokens.astype(U32), key_hi, key_lo)
