"""Fused multi-hash Pallas kernel: K independent Multilinear hashes per pass.

One launch evaluates K hash functions over a (B, N) token batch (DESIGN.md
§3): K stacked key windows are staged HBM->VMEM per n-tile alongside the
token tile, so the token bytes are read ONCE for all K functions -- the
k-probe Bloom workload, the two-level fingerprint tree, and the data
pipeline's dedup/split/shard triple expressed as a single grid.

Fused epilogue: the seed path ran the m1 add, the final >>32, and the
variable-length append-1 as separate XLA passes / host preprocessing
(`kernels/ops.py`, `core/multilinear.prepare_variable_length`). Here all
three live inside the kernel:

- per-row length codes (see `core.hostref.encode_lengths`) drive in-register
  masking: tokens beyond L read as 0, position L reads as the sentinel 1
  (variable-length rows), and key lanes beyond even(L+1) are zeroed so the
  HM family's (m+s)(m'+s') terms vanish exactly on padded lanes -- this is
  what makes the fused kernel bit-identical to the host append-1 policy for
  ragged per-row lengths in ALL families, not just MULTILINEAR;
- on the last n-tile the per-function m1 is added and the paper's `>> 32`
  is taken by writing the hi limb into the output slot.

Output is (B, K, 2) uint32 where [..., 0] is the finished 32-bit hash
(hi limb of m1 + sum) and [..., 1] the lo limb (so 64-bit fingerprint
consumers get the full accumulator from the same launch).

K is a static Python int: the per-function loop is unrolled at trace time
(K is small -- Bloom probes ~10), keeping per-step VMEM at the (block_b,
block_n) tile scale instead of materializing (K, block_b, block_n).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..core import limbs
from .multilinear import _digit_reduce_mod64

U32 = jnp.uint32
I32 = jnp.int32


def _mask_tile(toks, lens, j):
    """Apply the per-row length code to the j-th (bb, bn) tile of a row.

    `lens` is the (bb, 1) int32 length-code column of the tile's rows.
    Returns (tok_eff u32, live bool) where `live` masks the key lanes.
    Same algebra as core.hostref._mask_multi, expressed on the tile's
    global column indices; shared by the kernel body and the jnp oracle
    (which passes j=0 with the full width as one tile).
    """
    bb, bn = toks.shape
    col = j * bn + jax.lax.broadcasted_iota(I32, (bb, bn), 1)
    lens = lens.astype(I32)
    is_var = lens >= 0
    lm = jnp.where(is_var, lens, -lens - 1)
    tok_eff = jnp.where(
        col < lm, toks,
        jnp.where(is_var & (col == lm), np.uint32(1), np.uint32(0)),
    )
    end = lm + is_var.astype(I32)
    kend = end + (end & 1)  # ceil to even: HM pairs never straddle the mask
    return tok_eff, col < kend


def _lane_pairs(x):
    """HM pairing on lanes: (left, right, even) with left[:, j] = x[:, j],
    right[:, j] = x[:, j+1] and `even` the (1, bn-1) mask of the lanes j
    that start a pair. A pair product computed at every lane and masked to
    the even ones is the paper's (m_2i + s_2i)(m_2i+1 + s_2i+1) term: Mosaic
    has no lane-strided load and no (bb, bn) -> (bb, bn/2, 2) shape cast,
    but unit-offset lane slices lower everywhere. Tiles start at even
    columns (bn is even), so pairs never straddle two tiles."""
    bn = x.shape[-1]
    even = (jax.lax.broadcasted_iota(I32, (1, bn - 1), 1) & 1) == 0
    return x[:, :-1], x[:, 1:], even


def _multihash_kernel(tok_ref, kh_ref, kl_ref, len_ref, m1_ref, out_ref,
                      *, family: str, n_hashes: int, mod_m=None):
    """Grid cell (i, j): fold one (block_b, block_n) tile into K accumulators.

    j (the n axis) is the innermost grid dimension, so each row-block's
    output is revisited across j and finalized (m1 add + >>32) at the last j.
    """
    j = pl.program_id(1)
    toks = tok_ref[...]
    bb, bn = toks.shape
    tok_eff, live = _mask_tile(toks, len_ref[...], j)

    for k in range(n_hashes):
        kh = jnp.where(live, kh_ref[k : k + 1, :], np.uint32(0))
        kl = jnp.where(live, kl_ref[k : k + 1, :], np.uint32(0))
        if family in ("multilinear", "multilinear_2x2"):
            p_hi, p_lo = limbs.mul64_u32((kh, kl), tok_eff)
        else:  # multilinear_hm: (m + s)(m' + s') over lane pairs
            a_hi, a_lo = limbs.add64_u32((kh, kl), tok_eff)
            l_hi, r_hi, even = _lane_pairs(a_hi)
            l_lo, r_lo, _ = _lane_pairs(a_lo)
            p_hi, p_lo = limbs.mul64_low((l_hi, l_lo), (r_hi, r_lo))
            p_hi = jnp.where(even, p_hi, np.uint32(0))
            p_lo = jnp.where(even, p_lo, np.uint32(0))
        part_hi, part_lo = _digit_reduce_mod64(p_hi, p_lo, axis=1)

        @pl.when(j == 0)
        def _init(k=k, part_hi=part_hi, part_lo=part_lo):
            out_ref[:, k, 0] = part_hi
            out_ref[:, k, 1] = part_lo

        @pl.when(j > 0)
        def _acc(k=k, part_hi=part_hi, part_lo=part_lo):
            hi, lo = limbs.add64(
                (out_ref[:, k, 0], out_ref[:, k, 1]), (part_hi, part_lo))
            out_ref[:, k, 0] = hi
            out_ref[:, k, 1] = lo

    @pl.when(j == pl.num_programs(1) - 1)
    def _epilogue():
        # fused finish: + m1, then >>32 == "hash is the hi limb" (slot 0).
        # With mod_m the Bloom probe reduction also fuses here: slot 0 is
        # the full 64-bit accumulator mod m (limbs.mod_u64, DESIGN.md §2),
        # slot 1 keeps the finished 32-bit hash; a wide plan (m >= 2^32)
        # writes the residue's (hi, lo) limbs into slots 0 and 1 -- the
        # ModPlan reciprocal limbs are numpy-scalar literals, so the kernel
        # stays constant-free.
        for k in range(n_hashes):
            m1h = jnp.broadcast_to(m1_ref[k, 0], (bb,))
            m1l = jnp.broadcast_to(m1_ref[k, 1], (bb,))
            hi, lo = limbs.add64(
                (out_ref[:, k, 0], out_ref[:, k, 1]), (m1h, m1l))
            if mod_m is None:
                out_ref[:, k, 0] = hi
                out_ref[:, k, 1] = lo
            elif mod_m.wide:
                r_hi, r_lo = limbs.mod_u64((hi, lo), mod_m)
                out_ref[:, k, 0] = r_hi
                out_ref[:, k, 1] = r_lo
            else:
                out_ref[:, k, 0] = limbs.mod_u64((hi, lo), mod_m)
                out_ref[:, k, 1] = hi


@functools.partial(
    jax.jit,
    static_argnames=("family", "block_b", "block_n", "interpret", "mod_m"),
)
def multihash_blocks(
    tokens,
    key_hi,
    key_lo,
    lens,
    m1,
    *,
    family: str = "multilinear",
    block_b: int = 8,
    block_n: int = 1024,
    interpret: bool = False,
    mod_m=None,
):
    """Raw fused entry: (B, N) u32 tokens x (K, N) key planes -> (B, K, 2).

    B, N must be block multiples; key planes are the positional windows
    (WITHOUT m1 -- key_hi/lo[k, i] multiplies tokens[:, i]); m1 is (K, 2)
    uint32 (hi, lo); lens is the (B,) int32 length code. Output slot
    [..., 0] is the finished 32-bit hash, [..., 1] the lo limb.

    mod_m (a `limbs.ModPlan`, static): fuse the Bloom probe reduction into
    the epilogue -- slot [..., 0] becomes the full 64-bit accumulator mod m,
    slot [..., 1] the finished 32-bit hash; under a wide plan (m >= 2^32)
    slots [..., 0] and [..., 1] are the residue's hi and lo limbs.
    """
    B, N = tokens.shape
    K = key_hi.shape[0]
    assert key_hi.shape == key_lo.shape == (K, N), (key_hi.shape, K, N)
    assert m1.shape == (K, 2) and lens.shape == (B,)
    assert B % block_b == 0 and N % block_n == 0, (B, N, block_b, block_n)
    assert block_n <= 1 << 16, "digit-trick exactness bound"
    assert block_n % 2 == 0
    if family not in ("multilinear", "multilinear_2x2", "multilinear_hm"):
        raise ValueError(family)
    kernel = functools.partial(_multihash_kernel, family=family, n_hashes=K,
                               mod_m=mod_m)
    grid = (B // block_b, N // block_n)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((K, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((K, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((K, 2), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, K, 2), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, 2), U32),
        interpret=interpret,
        name="multihash",
    )(tokens.astype(U32), key_hi, key_lo, lens.astype(I32).reshape(B, 1), m1)
