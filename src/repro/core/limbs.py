"""32-bit limb arithmetic for mod-2^64 / mod-2^(32*n) integer math in JAX.

TPU v5e has no native 64-bit integer datapath: the VPU is 8x128 lanes of
32-bit ALUs. All ``mod 2^64`` arithmetic required by the Multilinear hash
families (Lemire & Kaser 2012, Thm 3.1) is therefore expressed over pairs
(hi, lo) of uint32 arrays. This module is the single source of truth for
that arithmetic; the Pallas kernels and the pure-jnp reference both use it.

A "u64" is a tuple ``(hi, lo)`` of equally-shaped uint32 arrays.
A "u32xN" multiword integer is a tuple of N uint32 limbs, little-endian
(``limbs[0]`` least significant) -- used for the K in {32,64,128} word-size
experiments of paper §3.2/§5.5.

All operations wrap silently (mod 2^32 per limb), matching unsigned C
semantics that the paper's implementations rely on.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32
# numpy scalar (not jnp): stays a literal in jaxprs, so Pallas kernel bodies
# using these helpers do not capture array constants.
_MASK16 = np.uint32(0xFFFF)


def _u32(x):
    return jnp.asarray(x, dtype=jnp.uint32)


# ---------------------------------------------------------------------------
# 32x32 -> 64 multiply via 16-bit halves (4 hardware multiplies).
# ---------------------------------------------------------------------------

def mul32_full(a, b):
    """Full 32x32 -> 64 product. Returns (hi, lo) uint32.

    Classic schoolbook on 16-bit digits; all intermediates provably fit in
    uint32 (see inline bounds). This is the TPU-native replacement for the
    x86 single-instruction 64-bit multiply the paper counts.
    """
    a = _u32(a)
    b = _u32(b)
    a_lo = a & _MASK16
    a_hi = a >> 16
    b_lo = b & _MASK16
    b_hi = b >> 16
    ll = a_lo * b_lo                      # <= (2^16-1)^2 < 2^32
    lh = a_lo * b_hi                      # < 2^32
    hl = a_hi * b_lo                      # < 2^32
    hh = a_hi * b_hi                      # < 2^32
    mid = lh + (ll >> 16)                 # <= 2^32-2^17+1 + 2^16-1 < 2^32
    mid2 = hl + (mid & _MASK16)           # < 2^32
    lo = (mid2 << 16) | (ll & _MASK16)
    hi = hh + (mid >> 16) + (mid2 >> 16)  # <= (2^16-1)^2 + 2^17 < 2^32
    return hi, lo


def mul32_lo(a, b):
    """Low 32 bits of a*b (native wrapping multiply)."""
    return _u32(a) * _u32(b)


# ---------------------------------------------------------------------------
# u64 = (hi, lo) ops
# ---------------------------------------------------------------------------

def u64(hi, lo):
    return _u32(hi), _u32(lo)


def u64_from_u32(x):
    x = _u32(x)
    return jnp.zeros_like(x), x


def add64(a, b):
    """(a_hi,a_lo) + (b_hi,b_lo) mod 2^64."""
    a_hi, a_lo = a
    b_hi, b_lo = b
    lo = a_lo + b_lo
    carry = (lo < a_lo).astype(U32)
    hi = a_hi + b_hi + carry
    return hi, lo


def add64_u32(a, x):
    """(hi,lo) + 32-bit x mod 2^64."""
    a_hi, a_lo = a
    x = _u32(x)
    lo = a_lo + x
    carry = (lo < x).astype(U32)
    return a_hi + carry, lo


def mul64_low(a, b):
    """Low 64 bits of (a_hi,a_lo) * (b_hi,b_lo).

    = full(a_lo,b_lo) + ((a_lo*b_hi + a_hi*b_lo) << 32).
    1 full (4 muls) + 2 low (2 muls) = 6 native 32-bit multiplies.
    """
    a_hi, a_lo = a
    b_hi, b_lo = b
    hi, lo = mul32_full(a_lo, b_lo)
    hi = hi + a_lo * b_hi + a_hi * b_lo
    return hi, lo


def mul64_u32(a, x):
    """Low 64 bits of (a_hi,a_lo) * x for 32-bit x.

    1 full (4 muls) + 1 low (1 mul) = 5 native multiplies. This is the
    inner-loop cost of MULTILINEAR per character on TPU limb arithmetic.
    """
    a_hi, a_lo = a
    x = _u32(x)
    hi, lo = mul32_full(a_lo, x)
    hi = hi + a_hi * x
    return hi, lo


def shr64_32(a):
    """(hi, lo) >> 32 -> uint32 (the paper's final `>> 32`)."""
    return a[0]


def unpack_bits32(x):
    """(...,) uint32 -> (..., 32) uint32 bit planes, LSB first.

    plane[..., j] = bit j of x. The avalanche/bit-independence metrics
    (repro.quality.metrics) and `Hasher.bit_planes` consume this; uint32
    output (not bool) so counts can be summed without a cast.
    """
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (_u32(x)[..., None] >> shifts) & np.uint32(1)


def u64_to_numpy(a):
    """Debug helper: (hi, lo) -> python-int-compatible numpy uint64."""
    import numpy as np

    hi = np.asarray(a[0], dtype=np.uint64)
    lo = np.asarray(a[1], dtype=np.uint64)
    return (hi << np.uint64(32)) | lo


# ---------------------------------------------------------------------------
# 64-mod-m digit reduction (DESIGN.md §2): h mod m for any m < 2^63,
# entirely in 32-bit ops, so Bloom probe indices never leave the device.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModPlan:
    """Frozen per-modulus aux for `mod_u64`/`mw_mod`.

    Carries the Barrett reciprocal M = floor(2^N / m) + 1 as three uint32
    limbs (little-endian), with N = 96 for m < 2^32 and N = 128 for
    m >= 2^32 (`wide`). All fields are python ints: the plan is hashable
    (jit static argument / kernel closure) and the limbs enter traced code
    as numpy-scalar literals, never captured array constants.

    Why these widths: for x < 2^F and m < 2^L the reciprocal at N = F + L
    bits makes the floor-division estimate EXACT (see `mod_u64`); with
    F = 64, L = 32 that is N = 96, so M fits three limbs for every
    non-power-of-two m >= 3 (M <= 2^96/3 + 1 < 2^95). A wide plan takes
    L = 64 (N = 128), and M still fits three limbs: m >= 2^32 + 1 gives
    M <= 2^128/(2^32 + 1) + 1 < 2^96. Powers of two (including m = 1)
    take the mask fast path and never consult M.
    """

    m: int
    is_pow2: bool
    mu0: int
    mu1: int
    mu2: int
    wide: bool = False  # m >= 2^32: the residue is two limbs (hi, lo)

    @classmethod
    def for_modulus(cls, m: int) -> "ModPlan":
        m = int(m)
        if not 1 <= m < 1 << 63:
            raise ValueError(f"modulus {m} outside the domain [1, 2^63)")
        wide = m >= 1 << 32
        if m & (m - 1) == 0:
            return cls(m=m, is_pow2=True, mu0=0, mu1=0, mu2=0, wide=wide)
        mu = (1 << (128 if wide else 96)) // m + 1
        return cls(m=m, is_pow2=False, mu0=mu & 0xFFFFFFFF,
                   mu1=(mu >> 32) & 0xFFFFFFFF, mu2=mu >> 64, wide=wide)


def _mul_limbs(a, b, n_out: int):
    """Low `n_out` limbs of the product of little-endian uint32 limb
    tuples `a` and `b` (schoolbook, carries propagated row by row: a
    column's acc + a_i*b_j + carry <= 2^64 - 1, so each carry fits a
    limb)."""
    zero = jnp.zeros_like(_u32(a[0]) + _u32(b[0]))
    acc = [zero] * n_out
    for i, ai in enumerate(a):
        carry = zero
        for j, bj in enumerate(b[: n_out - i]):
            hi, lo = mul32_full(ai, bj)
            s = acc[i + j] + lo
            c1 = (s < lo).astype(U32)
            t = s + carry
            c2 = (t < carry).astype(U32)
            acc[i + j] = t
            carry = hi + c1 + c2
        if i + len(b) < n_out:
            acc[i + len(b)] = carry
    return acc


def mod_u64(a, plan: ModPlan):
    """(hi, lo) uint32 64-bit value mod `plan.m` -> uint32 residue (< m),
    or, under a `wide` plan (m >= 2^32), the residue as (hi, lo) limbs.

    Power-of-two m: ``lo & (m-1)`` (m divides 2^32, the hi limb vanishes);
    wide, ``(hi & (m/2^32 - 1), lo)``.

    Otherwise the Lemire/Barrett direct-remainder form on 16-bit digits
    (every multiply below is `mul32_full`, i.e. four native 16-bit-digit
    multiplies): with M = floor(2^N/m) + 1,

        L = (M * x) mod 2^N           # fractional part of x/m, N-bit fixed
        r = floor(m * L / 2^N)        # scale the fraction back by m

    EXACTNESS (the correction-step bound, DESIGN.md §2): write
    2^N = k*m + rho (0 < rho < m, m not a power of two) so M = k + 1 and
    M*x = (2^N*x + b*x)/m with b = m - rho in [1, m-1]. Then
    L/2^N = (x mod m)/m + b*x/(m*2^N), and the error term obeys
    b*x < m * 2^64 <= 2^N -- for N = 96 because m < 2^32, for N = 128
    because m < 2^64 -- hence m*L/2^N < (x mod m) + 1 and the floor IS
    the remainder: the classic Barrett q-estimate correction step is
    provably never needed at either reciprocal width.

    Below 2^32 (N = 96) the limb products are written out by hand; a wide
    plan runs the generic `_mul_limbs` twice (L: 3 x 2 limbs truncated to
    4; r: limbs 4 and 5 of the 2 x 4-limb product m * L).
    """
    hi, lo = _u32(a[0]), _u32(a[1])
    if plan.wide:
        if plan.is_pow2:
            return hi & np.uint32((plan.m >> 32) - 1), lo
        mu = (np.uint32(plan.mu0), np.uint32(plan.mu1), np.uint32(plan.mu2))
        frac = _mul_limbs((lo, hi), mu, 4)
        m2 = (np.uint32(plan.m & 0xFFFFFFFF), np.uint32(plan.m >> 32))
        r = _mul_limbs(m2, frac, 6)
        return r[5], r[4]
    if plan.is_pow2:
        return lo & np.uint32(plan.m - 1)
    m32 = np.uint32(plan.m)
    mu0 = np.uint32(plan.mu0)
    mu1 = np.uint32(plan.mu1)
    mu2 = np.uint32(plan.mu2)
    # L = (M * x) mod 2^96, x = hi*2^32 + lo: 3 full + 2 low multiplies.
    # Contributions at limb 2 wrap mod 2^32 (== mod 2^96 overall); the
    # (mu2, hi) product lands entirely at limb 3 and is dropped.
    p0_hi, p0_lo = mul32_full(mu0, lo)
    p1_hi, p1_lo = mul32_full(mu0, hi)
    p2_hi, p2_lo = mul32_full(mu1, lo)
    s1 = p0_hi + p1_lo
    c1 = (s1 < p1_lo).astype(U32)
    l1 = s1 + p2_lo
    c2 = (l1 < p2_lo).astype(U32)
    l2 = p1_hi + p2_hi + mu1 * hi + mu2 * lo + c1 + c2
    # r = floor(m * L / 2^96) = limb 3 of the (m * L) product: 3 full
    # multiplies, carries propagated limb by limb.
    q0_hi, _ = mul32_full(m32, p0_lo)
    q1_hi, q1_lo = mul32_full(m32, l1)
    q2_hi, q2_lo = mul32_full(m32, l2)
    t1 = q0_hi + q1_lo
    c1 = (t1 < q1_lo).astype(U32)
    t2 = q1_hi + q2_lo
    ca = (t2 < q2_lo).astype(U32)
    t2c = t2 + c1
    cb = (t2c < c1).astype(U32)
    return q2_hi + ca + cb


def mw_mod(a, plan: ModPlan):
    """u32xN little-endian multiword mod `plan.m` -> uint32 residue (< m).

    Horner over 32-bit limbs from the most significant down: the running
    residue r < m makes every step value r*2^32 + limb < m*2^32 <= 2^64,
    i.e. exactly one `mod_u64` per limb. (Power-of-two m degenerates to
    ``a[0] & (m-1)`` through the same loop: each step discards r because
    m divides 2^32.) A wide plan breaks that bound and is refused.
    """
    if plan.wide:
        raise ValueError(f"mw_mod needs a modulus below 2^32, got {plan.m}")
    r = jnp.zeros_like(_u32(a[-1]))
    for limb in reversed(a):
        r = mod_u64((r, limb), plan)
    return r


# ---------------------------------------------------------------------------
# Generic little-endian multi-limb ops (K = 32*n bits), for §3.2/§5.5.
# ---------------------------------------------------------------------------

def mw_add(a, b):
    """Multiword add mod 2^(32n). a, b tuples of n uint32 limbs (LE)."""
    n = len(a)
    out = []
    carry = jnp.zeros_like(a[0])
    for i in range(n):
        s1 = a[i] + b[i]
        c1 = (s1 < a[i]).astype(U32)
        s2 = s1 + carry
        c2 = (s2 < s1).astype(U32)
        out.append(s2)
        carry = c1 + c2  # <= 1 each; total carry <= 1 effective next limb
    return tuple(out)


def mw_add_u32(a, x):
    n = len(a)
    out = []
    carry = _u32(x)
    for i in range(n):
        s = a[i] + carry
        carry = (s < carry).astype(U32)
        out.append(s)
    return tuple(out)


def mw_mul(a, b):
    """Multiword schoolbook product mod 2^(32n): n^2/2-ish native muls.

    Cost grows ~quadratically in limb count: this is the ``K^a`` (a≈1.58..2)
    superlinear multiplication cost that drives the paper's Eq. 5 sweet-spot
    analysis, reproduced on TPU limb arithmetic.
    """
    n = len(a)
    acc = [jnp.zeros_like(a[0]) for _ in range(n)]
    for i in range(n):
        carry = jnp.zeros_like(a[0])
        for j in range(n - i):
            hi, lo = mul32_full(a[i], b[j])
            k = i + j
            # acc[k] += lo + carry ; propagate into hi chain
            s1 = acc[k] + lo
            c1 = (s1 < lo).astype(U32)
            s2 = s1 + carry
            c2 = (s2 < carry).astype(U32)
            acc[k] = s2
            carry = hi + c1 + c2
        # drop final carry (mod 2^(32n))
    return tuple(acc)


def mw_shr_to_top(a, z_bits=32):
    """Return the top `z_bits`=32 limb: equivalent of `>> (K - 32)`."""
    return a[-1]
