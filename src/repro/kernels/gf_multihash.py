"""Fused multi-hash Pallas kernel for the GF(2^32) carry-less families.

The engine twin of `kernels/multihash.py` (DESIGN.md §3/§11): one launch
evaluates K independent GF MULTILINEAR / MULTILINEAR-HM hashes over a
(B, N) token batch, with the variable-length sentinel/mask, the m1 fold,
the Barrett polynomial reduction, and the optional `mod_m=` probe-index
reduction all fused into the same epilogue slots as the integer engine:

- slot [..., 0] = the finished 32-bit hash (Barrett-reduced accumulator);
- slot [..., 1] = the hi limb of the 63-bit xor-accumulator, so the
  engine's 64-bit surface `h64 = (hash32 << 32) | acc_hi` is a BIJECTION
  of the raw accumulator (Barrett's correction term depends on the hi limb
  alone: `hash32 = acc_lo ^ f(acc_hi)`, see `core.gf.barrett_reduce`) --
  64-bit consumers keep the accumulator's full entropy and the paper's
  "hi == the 32-bit hash" convention holds unchanged;
- with `mod_m=` (a static `limbs.ModPlan`): slot 0 = `h64 mod m` (the
  Bloom probe index -- identical to the host `h % m` formula on the
  uint64 surface), slot 1 = the finished 32-bit hash; a wide plan
  (m >= 2^32) puts the residue's hi and lo limbs in slots 0 and 1.

TPU has no CLMUL instruction (DESIGN.md §2): the 32x32 -> 63-bit carry-
less product is decomposed into 32 SHIFTED PARTIAL-PRODUCT PLANES
(`_clmul_tile`): plane i is the whole (bb, bn) operand tile shifted left
by i and gated by bit i of the other operand -- a rank-1 bit outer
product, which is exactly the formulation that maps onto int8-dot/MXU
units (each plane is a 1-bit x 32-bit dot contribution; 4 planes pack
into one int8 lane). On VPU/CPU backends the planes execute as 32
mask-xor steps; the plane decomposition is the single implementation the
jnp oracle (`ref.gf_multihash_ref`) shares, so every backend is
bit-identical by construction.

Masking is `multihash._mask_tile` -- the SAME length-code algebra as the
integer engine -- so ragged rows, the append-1 sentinel, and the HM
even-pad policy are family-independent: keys beyond even(L+1) are zeroed,
which makes the HM pairing terms (m ^ s)(m' ^ s') vanish exactly on dead
lanes (clmul(0, 0) = 0), mirroring the integer (m + s)(m' + s') == 0
policy bit for bit.

GF keys are 32-bit (`FamilyTraits.key_bits`): the engine consumes the LO
plane of the u64 Philox key streams; the hi plane rides unused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..core import gf as gf_core
from ..core import limbs
from .multihash import _lane_pairs, _mask_tile

U32 = jnp.uint32
I32 = jnp.int32


def _clmul_tile(a, b):
    """Carry-less 32x32 -> 63-bit product of two u32 tiles as (hi, lo).

    Shifted partial-product plane decomposition: the i-th plane is
    `a << i` (split across the lo/hi output limbs) gated by the lane mask
    of bit i of `b`. Unrolled at trace time -- 32 static planes, each a
    shift + mask + xor, with no cross-lane traffic (MXU-mappable, see
    module docstring). Bit-identical to `core.gf.clmul32` and the
    python-int `core.gf.clmul_ref` (pinned in tests/test_gf_engine.py).
    """
    acc_hi = jnp.zeros_like(a)
    acc_lo = jnp.zeros_like(a)
    for i in range(32):
        bit = (b >> np.uint32(i)) & np.uint32(1)
        mask = (jnp.uint32(0) - bit).astype(U32)
        acc_lo = acc_lo ^ ((a << np.uint32(i)) & mask)
        if i > 0:
            acc_hi = acc_hi ^ ((a >> np.uint32(32 - i)) & mask)
    return acc_hi, acc_lo


def _xor_reduce_tile(x):
    """Row-wise xor fold of a (bb, bn) tile -> (bb,).

    Mosaic lowers no xor reduction, so the fold halves the lane extent
    with static slices (aligned while the width is a multiple of 256, a
    few lane shifts below 128); an odd width peels its last column first.
    """
    tail = None
    while x.shape[1] > 1:
        w = x.shape[1]
        if w % 2:
            last = x[:, w - 1 :]
            tail = last if tail is None else tail ^ last
            x, w = x[:, : w - 1], w - 1
        x = x[:, : w // 2] ^ x[:, w // 2 :]
    return (x if tail is None else x ^ tail)[:, 0]


def _gf_multihash_kernel(tok_ref, kl_ref, len_ref, m1_ref, out_ref,
                         *, family: str, n_hashes: int, mod_m=None):
    """Grid cell (i, j): xor one (block_b, block_n) tile into K accumulators.

    Same grid contract as the integer `_multihash_kernel`: j (the n axis)
    is innermost, each row-block's output is revisited across j and
    finalized (m1 xor + Barrett + slot layout) at the last j.
    """
    j = pl.program_id(1)
    toks = tok_ref[...]
    bb, bn = toks.shape
    tok_eff, live = _mask_tile(toks, len_ref[...], j)

    for k in range(n_hashes):
        kl = jnp.where(live, kl_ref[k : k + 1, :], np.uint32(0))
        if family == "gf_multilinear":
            p_hi, p_lo = _clmul_tile(kl, tok_eff)
        else:  # gf_multilinear_hm: (m ^ s) clmul (m' ^ s') over lane pairs
            left, right, even = _lane_pairs(kl ^ tok_eff)
            p_hi, p_lo = _clmul_tile(left, right)
            p_hi = jnp.where(even, p_hi, np.uint32(0))
            p_lo = jnp.where(even, p_lo, np.uint32(0))
        part_hi = _xor_reduce_tile(p_hi)
        part_lo = _xor_reduce_tile(p_lo)

        @pl.when(j == 0)
        def _init(k=k, part_hi=part_hi, part_lo=part_lo):
            out_ref[:, k, 0] = part_hi
            out_ref[:, k, 1] = part_lo

        @pl.when(j > 0)
        def _acc(k=k, part_hi=part_hi, part_lo=part_lo):
            out_ref[:, k, 0] = out_ref[:, k, 0] ^ part_hi
            out_ref[:, k, 1] = out_ref[:, k, 1] ^ part_lo

    @pl.when(j == pl.num_programs(1) - 1)
    def _epilogue():
        # fused finish: xor m1 (32-bit, lo limb only), Barrett-reduce, then
        # lay out the integer engine's slot contract on the 64-bit surface
        # h64 = (hash32 << 32) | acc_hi (see module docstring). With mod_m
        # the probe reduction also fuses here: `limbs.mod_u64` on the
        # (hash32, acc_hi) limbs == the host `h64 % m`.
        for k in range(n_hashes):
            acc_hi = out_ref[:, k, 0]
            acc_lo = out_ref[:, k, 1] ^ jnp.broadcast_to(m1_ref[k, 1], (bb,))
            h32 = gf_core.barrett_reduce(acc_hi, acc_lo)
            if mod_m is None:
                out_ref[:, k, 0] = h32
                out_ref[:, k, 1] = acc_hi
            elif mod_m.wide:
                r_hi, r_lo = limbs.mod_u64((h32, acc_hi), mod_m)
                out_ref[:, k, 0] = r_hi
                out_ref[:, k, 1] = r_lo
            else:
                out_ref[:, k, 0] = limbs.mod_u64((h32, acc_hi), mod_m)
                out_ref[:, k, 1] = h32


@functools.partial(
    jax.jit,
    static_argnames=("family", "block_b", "block_n", "interpret", "mod_m"),
)
def gf_multihash_blocks(
    tokens,
    key_lo,
    lens,
    m1,
    *,
    family: str = "gf_multilinear",
    block_b: int = 8,
    block_n: int = 1024,
    interpret: bool = False,
    mod_m=None,
):
    """Raw fused GF entry: (B, N) u32 tokens x (K, N) key plane -> (B, K, 2).

    The carry-less twin of `multihash.multihash_blocks`, same contract:
    B, N must be block multiples; `key_lo` is the positional 32-bit key
    window (WITHOUT m1 -- key_lo[k, i] multiplies tokens[:, i]); `m1` is
    (K, 2) uint32 for interface symmetry with the integer engine (the hi
    limb is ignored -- GF m1 is 32-bit); `lens` is the (B,) int32 length
    code. Output slot [..., 0] is the finished 32-bit hash, [..., 1] the
    accumulator hi limb (together: h64, see module docstring).

    mod_m (a `limbs.ModPlan`, static): fuse the probe reduction into the
    epilogue -- slot [..., 0] becomes h64 mod m, slot [..., 1] the
    finished 32-bit hash (a wide plan: the residue's hi and lo limbs).
    """
    B, N = tokens.shape
    K = key_lo.shape[0]
    assert key_lo.shape == (K, N), (key_lo.shape, K, N)
    assert m1.shape == (K, 2) and lens.shape == (B,)
    assert B % block_b == 0 and N % block_n == 0, (B, N, block_b, block_n)
    assert block_n <= 1 << 16
    assert block_n % 2 == 0
    if family not in ("gf_multilinear", "gf_multilinear_hm"):
        raise ValueError(family)
    kernel = functools.partial(_gf_multihash_kernel, family=family,
                               n_hashes=K, mod_m=mod_m)
    grid = (B // block_b, N // block_n)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((K, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((K, 2), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, K, 2), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, 2), U32),
        interpret=interpret,
        name="gf_multihash",
    )(tokens.astype(U32), key_lo, lens.astype(I32).reshape(B, 1), m1)
