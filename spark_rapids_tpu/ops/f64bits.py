"""TPU-safe float64 bit access via double-double (dd) decomposition.

XLA:TPU has no native f64.  With x64 enabled, the X64 rewriter emulates
f64 as a pair of f32 values ("double-double": value = hi + lo with
|lo| <= ulp(hi)/2), giving ~49-bit precision and the f32 exponent range
(~1e+/-38).  Crucially, the rewriter does NOT implement
``bitcast_convert_type`` from f64 to any integer type — every bit-level
trick the reference uses on doubles (cuDF sort-key normalization,
murmur3 over IEEE bytes: spark-rapids HashFunctions.scala,
SortUtils.scala) needs a TPU-native reformulation.  This module is that
reformulation:

- ``dd_split(x)``: (hi_f32, lo_f32) with hi = f32(x), lo = f32(x - hi).
  Exact and *injective* on device-representable doubles: hi is a
  monotone function of x and (hi, lo) reconstructs x exactly, so
  equality and lexicographic order of the pair match the double's
  equality and order.  Two 32-bit bitcasts (which TPU supports) then
  yield integer words for sorting, grouping and join-key hashing.
- ``f64_ieee_bits(x)``: reassembles the IEEE-754 bit pattern of the
  (rounded-to-f64) device value as an int64 using only arithmetic and
  32-bit bitcasts — used by the Spark-compatible murmur3/xxhash64
  device paths.  For any value that is exactly representable on device
  (all f32-exact doubles, integers up to 2^48, etc.) this matches
  Spark's hash bit-for-bit.

Everything here canonicalizes -0.0 -> 0.0 and NaN -> one canonical NaN
first (Spark sort/hash semantics; reference NormalizeFloatingNumbers).
"""

from __future__ import annotations

import numpy as np

_EXP_MASK = np.int64(0x7FF0000000000000)
_NAN_BITS = np.int64(0x7FF8000000000000)
_MANT_MASK = np.int64((1 << 52) - 1)

_BITCAST64: "bool | None" = None


def f64_bitcast_ok() -> bool:
    """Does the active JAX backend support 64-bit float bitcasts?

    True on CPU/GPU (real binary64 — the single u64 word is exact and
    the dd split would LOSE precision there), False on TPU (dd
    emulation: the X64 rewriter has no f64 bitcast, and the dd split
    loses nothing because dd *is* the representation).  Decided from
    the backend name — a probe compile would deadlock when first hit
    inside another program's trace.
    """
    global _BITCAST64
    if _BITCAST64 is None:
        import jax
        _BITCAST64 = jax.default_backend() != "tpu"
    return _BITCAST64


def dd_canonical(x, jnp):
    """-0.0 -> 0.0, every NaN -> canonical NaN (float32 or float64)."""
    zero = jnp.asarray(0, dtype=x.dtype)
    x = jnp.where(x == zero, zero, x)
    return jnp.where(jnp.isnan(x), jnp.asarray(np.nan, dtype=x.dtype), x)


def dd_split(x, jnp):
    """f64 -> (hi_f32, lo_f32) with x == hi + lo exactly (device dd).

    Monotone in hi, injective as a pair; lo is +/-0-free only through
    canonicalization by the caller's word transform.
    """
    hi = x.astype(np.float32)
    lo = (x - hi.astype(x.dtype)).astype(np.float32)
    return hi, lo


def f32_sortable_u32(x, jnp):
    """IEEE f32 -> uint32 whose unsigned order == float total order
    (-NaN-free: NaN canonicalized to positive, sorts above +inf;
    -0.0 == 0.0).  Same trick as cuDF/radix-sort key normalization.

    Canonicalization happens at the BIT level: an arithmetic ``x == 0``
    compare would flush f32-subnormal magnitudes to zero on TPU,
    collapsing distinct tiny doubles into one sort/group/hash key."""
    import jax
    u = jax.lax.bitcast_convert_type(x, np.uint32)
    u = jnp.where(u == np.uint32(0x80000000), np.uint32(0), u)  # -0.0
    u = jnp.where(jnp.isnan(x), np.uint32(0x7FC00000), u)       # canon NaN
    sign = np.uint32(0x80000000)
    return jnp.where((u & sign) != 0, u ^ np.uint32(0xFFFFFFFF), u | sign)


def f64_sortable_words(x, jnp):
    """f64 -> order- and equality-preserving unsigned words.

    Backends with a real binary64 (CPU): one exact uint64 word via the
    classic sign-flip bitcast.  TPU (dd emulation, no f64 bitcast): TWO
    uint32 words from the dd split, each f32-normalized.  Why the pair
    works: hi = f32(x) is monotone non-decreasing in x, and for equal hi
    the order of x equals the order of lo = x - hi.  +/-inf: lo becomes
    NaN (inf - inf), identical for all same-signed infinities so
    equality holds; NaN x sorts above +inf via the hi word alone.
    """
    import jax
    if f64_bitcast_ok():
        x = dd_canonical(x, jnp)
        u = jax.lax.bitcast_convert_type(x, np.uint64)
        sign = np.uint64(1) << np.uint64(63)
        return [jnp.where((u & sign) != 0, u ^ ~np.uint64(0), u | sign)]
    # no arithmetic canonicalization on the dd path (a == 0 compare would
    # flush f32-subnormal hi parts); each f32 word canonicalizes by bits.
    hi, lo = dd_split(x, jnp)
    return [f32_sortable_u32(hi, jnp), f32_sortable_u32(lo, jnp)]


def f64_word_count() -> int:
    """How many unsigned words f64_sortable_words yields on this backend
    (join-side width agreement)."""
    return 1 if f64_bitcast_ok() else 2


def _exp2_small(e, dtype, jnp):
    """Exact 2.0**e for integer |e| <= 64 (bit-ladder of exact
    power-of-two constants; every intermediate <= 2^64, dd-safe)."""
    neg = e < 0
    a = jnp.abs(e)
    r = jnp.ones(e.shape, dtype=dtype)
    for k in range(7):  # bits 1..64
        c = jnp.asarray(float(2.0 ** (2 ** k)), dtype=dtype)
        r = r * jnp.where((a >> k) & 1 == 1, c, jnp.ones_like(r))
    return jnp.where(neg, 1.0 / r, r)


def scale_exp2(x, e, jnp):
    """x * 2.0**e exactly, |e| <= 320, without materializing 2**e
    (which would overflow the dd exponent range): +/-64 chunks applied
    multiplicatively, each partial product stays between x and the
    (in-range) target."""
    r = x
    rem = e
    for _ in range(5):
        step = jnp.clip(rem, -64, 64)
        r = r * _exp2_small(step, x.dtype, jnp)
        rem = rem - step
    return r


def f64_ieee_bits(x, jnp):
    """Device f64 -> int64 IEEE-754 bit pattern of the value rounded to
    binary64, via arithmetic exponent/mantissa extraction (no 64-bit
    bitcasts).  Canonicalizes -0.0 and NaN.

    Zero/tiny classification happens at the BIT level of the dd words
    (dd_split + 32-bit bitcasts, like f64_sortable_words): arithmetic
    ``x == 0`` compares flush f32-subnormal magnitudes on TPU, which
    would collapse distinct tiny keys to the bits of +0.0 and diverge
    from the CPU oracle's exact bitcast (ADVICE r3).  Values whose hi
    word is f32-subnormal (|x| < 2^-126; the dd representation bottoms
    out at 2^-149, where lo is always ±0) get their bits reassembled
    from the hi word's integer mantissa directly — arithmetic on such
    magnitudes would flush.
    """
    import jax
    if f64_bitcast_ok():
        x = dd_canonical(x, jnp)
        return jax.lax.bitcast_convert_type(x, np.int64)
    isnan = jnp.isnan(x)
    isinf = jnp.isinf(x)
    hi, lo = dd_split(x, jnp)
    uh = jax.lax.bitcast_convert_type(hi, np.uint32)
    ul = jax.lax.bitcast_convert_type(lo, np.uint32)
    mag_h = uh & np.uint32(0x7FFFFFFF)
    mag_l = ul & np.uint32(0x7FFFFFFF)
    nonzero = (mag_h != 0) | (mag_l != 0)
    # hi in the f32-subnormal range: exponent bits all zero, mantissa set
    tiny = (mag_h >> np.uint32(23) == 0) & nonzero & ~isnan & ~isinf
    finite = ~isnan & ~isinf & nonzero & ~tiny
    a = jnp.abs(jnp.where(finite, x, jnp.ones_like(x)))
    # lift near-f32-subnormal magnitudes into the safe range (exact scale)
    small = a < 2.0 ** -60
    a = a * jnp.where(small, jnp.asarray(2.0 ** 64, a.dtype),
                      jnp.ones_like(a))
    off = jnp.where(small, -64, 0).astype(np.int32)
    # exponent estimate from the f32 hi part, corrected by one step
    ua = jax.lax.bitcast_convert_type(a.astype(np.float32), np.uint32)
    e0 = ((ua >> np.uint32(23)) & np.uint32(0xFF)).astype(np.int32) - 127
    m0 = scale_exp2(a, -e0, jnp)
    e1 = e0 + jnp.where(m0 >= 2.0, 1, 0) - jnp.where(m0 < 1.0, 1, 0)
    m = scale_exp2(a, -e1, jnp)           # in [1, 2)
    exp = (e1 + off).astype(np.int64)
    mant = (m * (2.0 ** 52)).astype(np.int64) - np.int64(1 << 52)
    mant = jnp.clip(mant, 0, _MANT_MASK)
    bits = ((exp + np.int64(1023)) << np.int64(52)) | mant
    bits = jnp.where(finite, bits, np.int64(0))
    # tiny path: |x| = m_int * 2^-149 exactly (m_int = hi's 23 mantissa
    # bits; lo is ±0 here).  floor(log2 m_int) comes from the exact
    # f32 representation of the INTEGER m_int — integer bit math only,
    # no flushable arithmetic.
    m_int = mag_h.astype(np.int64)
    m_f = jnp.maximum(m_int, 1).astype(np.float32)    # exact for < 2^24
    um = jax.lax.bitcast_convert_type(m_f, np.uint32)
    e_m = ((um >> np.uint32(23)) & np.uint32(0xFF)).astype(np.int64) - 127
    t_exp = e_m - 149
    t_mant = (jnp.left_shift(m_int, (52 - e_m)) - np.int64(1 << 52)) \
        & _MANT_MASK
    t_bits = ((t_exp + np.int64(1023)) << np.int64(52)) | t_mant
    bits = jnp.where(tiny, t_bits, bits)
    bits = jnp.where(isinf, _EXP_MASK, bits)
    bits = jnp.where(isnan, _NAN_BITS, bits)
    # sign from the hi word's bit, canonicalized: -0.0 -> +0.0, NaN -> +
    neg = (uh >> np.uint32(31) != 0) & nonzero & ~isnan
    sign = jnp.where(neg, np.int64(-2 ** 63), np.int64(0))
    return bits | sign
