"""jit'd public wrappers around the Pallas hash kernels.

Handles: block-multiple zero-padding of tokens AND keys (value-preserving,
see multilinear.py docstring), m1 offset, the final >>32, family dispatch,
and backend selection (Pallas kernel on TPU, interpret-mode on CPU, or the
fused jnp reference -- whichever the caller asks for).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import gf as gf_core
from ..core import limbs
from . import gf_multihash as gfmh
from . import gf_multilinear as gfk
from . import multihash as mhk
from . import multilinear as mlk
from . import ref

U32 = jnp.uint32

# Python-level dispatch counter: one increment == one device launch (pallas /
# interpret pallas_call or one fused-jnp jit call). Tests use this to prove
# batch consumers (Bloom admission etc.) issue exactly ONE launch per batch.
_LAUNCHES = [0]


def launch_count() -> int:
    return _LAUNCHES[0]


@functools.partial(
    jax.jit, static_argnames=("family", "block_b", "block_n", "backend",
                              "mod_m")
)
def _multihash_jit(tokens, key_hi, key_lo, lens, m1, *, family, block_b,
                   block_n, backend, mod_m):
    if family.startswith("gf_"):
        # carry-less engine: 32-bit keys -- the hi plane is dead weight
        # here (DCE'd under jit), kept in the signature so every caller
        # stages key planes identically across families
        if backend == "jnp":
            return ref.gf_multihash_ref(tokens, key_lo, lens, m1,
                                        family=family, mod_m=mod_m)
        return gfmh.gf_multihash_blocks(
            tokens, key_lo, lens, m1,
            family=family, block_b=block_b, block_n=block_n,
            interpret=(backend == "interpret"), mod_m=mod_m,
        )
    if backend == "jnp":
        return ref.multihash_ref(tokens, key_hi, key_lo, lens, m1,
                                 family=family, mod_m=mod_m)
    return mhk.multihash_blocks(
        tokens, key_hi, key_lo, lens, m1,
        family=family, block_b=block_b, block_n=block_n,
        interpret=(backend == "interpret"), mod_m=mod_m,
    )


def multihash(tokens, key_hi, key_lo, lens, m1, *, family="multilinear",
              block_b=8, block_n=1024, backend="interpret", mod_m=None):
    """Fused multi-hash launch: (B, N) x (K, N) key planes -> (B, K, 2) u32.

    Inputs must already be block-aligned/padded (core.ops owns padding and
    key staging); this layer owns backend dispatch and launch accounting.
    backend: 'pallas' (TPU), 'interpret' (kernel body on CPU), 'jnp' (fused
    oracle -- the fast CPU production path).
    mod_m (a `limbs.ModPlan`, static): fuse the probe reduction into the
    epilogue -- output slot 0 = accumulator mod m, slot 1 = 32-bit hash
    (a wide plan, m >= 2^32: the residue's hi and lo limbs).
    """
    _LAUNCHES[0] += 1
    return _multihash_jit(
        tokens, key_hi, key_lo, lens, m1,
        family=family, block_b=block_b, block_n=block_n, backend=backend,
        mod_m=mod_m,
    )


def _pad_to(x, n, axis=-1):
    pad = n - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


@functools.partial(
    jax.jit,
    static_argnames=("family", "block_b", "block_n", "backend"),
)
def multilinear_hash(
    tokens,
    key_hi,
    key_lo,
    *,
    family: str = "multilinear",
    block_b: int = mlk.DEFAULT_BLOCK_B,
    block_n: int = mlk.DEFAULT_BLOCK_N,
    backend: str = "interpret",
):
    """Batched (B, N) -> (B,) uint32 Multilinear hash.

    key_hi/key_lo: (>= N+1,) uint32 planes; key 0 is m1 (paper convention).
    backend: 'pallas' (TPU), 'interpret' (kernel body on CPU), 'jnp' (oracle).
    """
    toks = jnp.atleast_2d(jnp.asarray(tokens)).astype(U32)
    B, N = toks.shape
    kh = jnp.asarray(key_hi)[1 : N + 1]
    kl = jnp.asarray(key_lo)[1 : N + 1]
    m1 = (key_hi[0], key_lo[0])

    if backend == "jnp":
        acc = ref.multilinear_accumulate_ref(toks, kh, kl, family=family)
    else:
        Bp = -(-B // block_b) * block_b
        Np = -(-N // block_n) * block_n
        toks_p = _pad_to(_pad_to(toks, Np, axis=1), Bp, axis=0)
        kh_p = _pad_to(kh, Np)
        kl_p = _pad_to(kl, Np)
        acc = mlk.hash_blocks(
            toks_p, kh_p, kl_p,
            family=family, block_b=block_b, block_n=block_n,
            interpret=(backend == "interpret"),
        )[:B]
    total = limbs.add64(
        (acc[:, 0], acc[:, 1]),
        (jnp.broadcast_to(m1[0], acc[:, 0].shape), jnp.broadcast_to(m1[1], acc[:, 1].shape)),
    )
    out = limbs.shr64_32(total)
    return out if jnp.asarray(tokens).ndim > 1 else out[0]


@functools.partial(
    jax.jit, static_argnames=("family", "block_b", "block_n", "backend")
)
def gf_hash(
    tokens,
    keys32,
    *,
    family: str = "gf_multilinear",
    block_b: int = 8,
    block_n: int = 512,
    backend: str = "interpret",
):
    """Batched (B, N) -> (B,) uint32 GF(2^32) Multilinear hash (Barrett)."""
    toks = jnp.atleast_2d(jnp.asarray(tokens)).astype(U32)
    B, N = toks.shape
    k = jnp.asarray(keys32)[1 : N + 1]
    m1 = keys32[0]

    if backend == "jnp":
        acc = ref.gf_accumulate_ref(toks, k, family=family)
    else:
        Bp = -(-B // block_b) * block_b
        Np = -(-N // block_n) * block_n
        toks_p = _pad_to(_pad_to(toks, Np, axis=1), Bp, axis=0)
        k_p = _pad_to(k, Np)
        acc = gfk.gf_hash_blocks(
            toks_p, k_p, family=family, block_b=block_b, block_n=block_n,
            interpret=(backend == "interpret"),
        )[:B]
    out = gf_core.barrett_reduce(acc[:, 0], acc[:, 1] ^ m1)
    return out if jnp.asarray(tokens).ndim > 1 else out[0]


def hash_tokens_batched(tokens: np.ndarray, family: str = "multilinear_hm", seed: int = 0x1E53, **kw):
    """Convenience: numpy in/out, global key buffer, variable-length policy
    NOT applied (fixed-shape batch)."""
    from ..core.keys import KeyBuffer

    toks = np.atleast_2d(np.asarray(tokens, np.uint32))
    kb = KeyBuffer(seed=seed)
    n = toks.shape[1]
    if family.startswith("gf"):
        lo = kb.hi_lo(n + 1)[1]
        return np.asarray(gf_hash(toks, jnp.asarray(lo), family=family, **kw))
    hi, lo = kb.hi_lo(n + 1)
    return np.asarray(
        multilinear_hash(toks, jnp.asarray(hi), jnp.asarray(lo), family=family, **kw)
    )
