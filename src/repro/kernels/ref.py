"""Pure-jnp oracles for the Pallas kernels (same signatures as ops.py).

These ARE the reference implementations of record: the kernels must match
them bit-exactly for every shape/dtype in the sweep tests, and they in turn
match the numpy-uint64 / python-int oracles in tests/test_core_*.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core import gf as gf_core
from ..core import limbs
from ..core import multilinear as ml


def multilinear_accumulate_ref(tokens, key_hi, key_lo, family="multilinear"):
    """(B, N) x (N,) keys (no m1) -> (B, 2) uint32 (hi, lo) of sum m_i s_i."""
    toks = jnp.asarray(tokens).astype(jnp.uint32)
    if family in ("multilinear", "multilinear_2x2"):
        p_hi, p_lo = limbs.mul64_u32((key_hi[None, :], key_lo[None, :]), toks)
    elif family == "multilinear_hm":
        a = limbs.add64_u32((key_hi[None, 0::2], key_lo[None, 0::2]), toks[:, 0::2])
        b = limbs.add64_u32((key_hi[None, 1::2], key_lo[None, 1::2]), toks[:, 1::2])
        p_hi, p_lo = limbs.mul64_low(a, b)
    else:
        raise ValueError(family)
    hi, lo = ml._reduce_sum64((p_hi, p_lo), axis=-1)
    return jnp.stack([hi, lo], axis=-1)


def multihash_ref(tokens, key_hi, key_lo, lens, m1, family="multilinear",
                  mod_m=None):
    """Pure-jnp oracle of the fused multi-hash kernel: (B, N) -> (B, K, 2).

    Same semantics as `multihash.multihash_blocks` (length-code masking,
    m1 add, hash32 in slot 0; with mod_m the slot-0 probe reduction and
    slot-1 hash32, or a wide plan's residue limbs in both) with the K loop
    unrolled over limb-jnp ops.
    """
    from .multihash import _mask_tile

    toks = jnp.asarray(tokens).astype(jnp.uint32)
    B, N = toks.shape
    K = key_hi.shape[0]
    # one "tile" spanning the whole array (j=0) -> same masking algebra as
    # the kernel, single source of truth
    tok_eff, live = _mask_tile(toks, jnp.asarray(lens)[:, None],
                               jnp.int32(0))
    outs = []
    for k in range(K):
        kh = jnp.where(live, key_hi[k][None, :], np.uint32(0))
        kl = jnp.where(live, key_lo[k][None, :], np.uint32(0))
        if family in ("multilinear", "multilinear_2x2"):
            p_hi, p_lo = limbs.mul64_u32((kh, kl), tok_eff)
        elif family == "multilinear_hm":
            a = limbs.add64_u32((kh[:, 0::2], kl[:, 0::2]), tok_eff[:, 0::2])
            b = limbs.add64_u32((kh[:, 1::2], kl[:, 1::2]), tok_eff[:, 1::2])
            p_hi, p_lo = limbs.mul64_low(a, b)
        else:
            raise ValueError(family)
        hi, lo = ml._reduce_sum64((p_hi, p_lo), axis=-1)
        hi, lo = limbs.add64(
            (hi, lo),
            (jnp.broadcast_to(m1[k, 0], hi.shape),
             jnp.broadcast_to(m1[k, 1], lo.shape)))
        if mod_m is not None:
            r = limbs.mod_u64((hi, lo), mod_m)
            outs.append(jnp.stack(list(r) if mod_m.wide else [r, hi],
                                  axis=-1))
        else:
            outs.append(jnp.stack([hi, lo], axis=-1))
    return jnp.stack(outs, axis=1)


def gf_multihash_ref(tokens, key_lo, lens, m1, family="gf_multilinear",
                     mod_m=None):
    """Pure-jnp oracle of the fused GF multi-hash kernel: (B, N) -> (B, K, 2).

    Same semantics as `gf_multihash.gf_multihash_blocks` (length-code
    masking, m1 xor, Barrett, hash32 in slot 0 / accumulator hi limb in
    slot 1; with mod_m the slot-0 probe reduction and slot-1 hash32, or a
    wide plan's residue limbs in both) with
    the K loop unrolled over the shared partial-product-plane clmul.
    """
    from .gf_multihash import _clmul_tile, _xor_reduce_tile
    from .multihash import _mask_tile

    toks = jnp.asarray(tokens).astype(jnp.uint32)
    B, N = toks.shape
    K = key_lo.shape[0]
    tok_eff, live = _mask_tile(toks, jnp.asarray(lens)[:, None],
                               jnp.int32(0))
    outs = []
    for k in range(K):
        kl = jnp.where(live, key_lo[k][None, :], np.uint32(0))
        if family == "gf_multilinear":
            p_hi, p_lo = _clmul_tile(kl, tok_eff)
        elif family == "gf_multilinear_hm":
            p_hi, p_lo = _clmul_tile(kl[:, 0::2] ^ tok_eff[:, 0::2],
                                     kl[:, 1::2] ^ tok_eff[:, 1::2])
        else:
            raise ValueError(family)
        acc_hi = _xor_reduce_tile(p_hi)
        acc_lo = _xor_reduce_tile(p_lo) ^ jnp.broadcast_to(m1[k, 1],
                                                           (B,)).astype(
            jnp.uint32)
        h32 = gf_core.barrett_reduce(acc_hi, acc_lo)
        if mod_m is not None:
            r = limbs.mod_u64((h32, acc_hi), mod_m)
            outs.append(jnp.stack(list(r) if mod_m.wide else [r, h32],
                                  axis=-1))
        else:
            outs.append(jnp.stack([h32, acc_hi], axis=-1))
    return jnp.stack(outs, axis=1)


def gf_accumulate_ref(tokens, keys32, family="gf_multilinear"):
    """(B, N) x (N,) keys -> (B, 2) uint32 xor-accumulators (hi, lo)."""
    toks = jnp.asarray(tokens).astype(jnp.uint32)
    if family == "gf_multilinear":
        p_hi, p_lo = gf_core.clmul32(keys32[None, :], toks)
    elif family == "gf_multilinear_hm":
        a = keys32[None, 0::2] ^ toks[:, 0::2]
        b = keys32[None, 1::2] ^ toks[:, 1::2]
        p_hi, p_lo = gf_core.clmul32(a, b)
    else:
        raise ValueError(family)
    hi = gf_core._xor_reduce(p_hi)
    lo = gf_core._xor_reduce(p_lo)
    return jnp.stack([hi, lo], axis=-1)
