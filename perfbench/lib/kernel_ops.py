"""Work of the Ed25519 verify kernel per signature, counted from its
algorithm and shapes (ops/ed25519_jax.py verify_core, which the Pallas
kernel runs per 1,024-lane block).

This is the numerator a roofline share of the kernel will use once a
published int32 VPU peak of the v5e exists (PERF.md, Open questions). It
is kept here, with the benchmark, so no PR that claims a gain can change
how the work is counted. tests/test_harness.py ties it to the program by
counting the field multiplications a trace of verify_core performs.
"""

from __future__ import annotations

P = 2 ** 255 - 19
NLIMBS = 20  # radix 2^13 limbs of a field element
EXT_ADD_MULS = 9  # add-2008-hwcd-3 with the 2d product: 8 + 1
EXT_DBL_MULS = 8  # dbl-2008-hwcd: 4 squarings + 4 products
WINDOWS = 64  # 4-bit windows of a 256-bit scalar
TABLE_ADDS = 14  # [2..15](-A) from -A by repeated addition


def pow_muls(exponent: int) -> int:
    """Square-and-multiply as the kernel runs it: every step squares and
    multiplies (a select keeps one), over all bits after the leading one."""
    return 2 * (exponent.bit_length() - 1)


def field_muls_per_sig() -> int:
    decompress = 13 + pow_muls((P - 5) // 8)
    table = TABLE_ADDS * EXT_ADD_MULS
    ladder = WINDOWS * (4 * EXT_DBL_MULS + 2 * EXT_ADD_MULS)
    encode = 2 + pow_muls(P - 2)
    return decompress + table + ladder + encode


def int32_mul_ops_per_sig() -> int:
    """Limb products: each field multiplication is a 20 x 20 convolution."""
    return field_muls_per_sig() * NLIMBS * NLIMBS


def bytes_in_per_sig() -> int:
    """Bytes the kernel reads per lane: A, R, S and h as 8 uint32 words."""
    return 4 * 8 * 4
