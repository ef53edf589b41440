"""The plain reference for signature verdicts: Ed25519 verification as
RFC 8032 section 5.1.7 writes it, in Python integers, importing nothing
of the program.

The configuration states the accept set the program keeps (Corda's
i2p EdDSAEngine, which the program's host tier matches). Against
RFC 8032 that set departs in three places, which this reference follows
and names:
  * the check is cofactorless, [S]B = R + [h]A, by comparing the
    encoding of [S]B - [h]A with the 32 bytes of R as sent;
  * S is not checked against L;
  * a y coordinate >= p is reduced mod p instead of refused, and a zero
    x with the sign bit set is not refused.
"""

from __future__ import annotations

import hashlib

P = 2 ** 255 - 19
L = 2 ** 252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)


def _recover_x(y: int, sign: int):
    xx = (y * y - 1) * pow(D * y * y + 1, P - 2, P) % P
    x = pow(xx, (P + 3) // 8, P)
    if (x * x - xx) % P != 0:
        x = x * SQRT_M1 % P
    if (x * x - xx) % P != 0:
        return None
    if x & 1 != sign:
        x = P - x if x else 0
    return x


BY = 4 * pow(5, P - 2, P) % P
BASE = (_recover_x(BY, 0), BY, 1, _recover_x(BY, 0) * BY % P)
IDENTITY = (0, 1, 1, 0)


def _add(p, q):
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = 2 * D * t1 * t2 % P
    d = 2 * z1 * z2 % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def _encode(pt) -> bytes:
    x, y, z, _ = pt
    zi = pow(z, P - 2, P)
    x, y = x * zi % P, y * zi % P
    return (y | (x & 1) << 255).to_bytes(32, "little")


def _decode(enc: bytes):
    n = int.from_bytes(enc, "little")
    y = (n & ((1 << 255) - 1)) % P
    x = _recover_x(y, n >> 255)
    if x is None:
        return None
    return (x, y, 1, x * y % P)


def verify(pubkey: bytes, message: bytes, sig: bytes) -> bool:
    """True when `sig` by `pubkey` covers `message`; malformed input is
    False, never an exception."""
    if len(pubkey) != 32 or len(sig) != 64:
        return False
    a = _decode(pubkey)
    if a is None:
        return False
    neg_a = ((P - a[0]) % P, a[1], 1, (P - a[3]) % P)
    s = int.from_bytes(sig[32:], "little")
    h = int.from_bytes(hashlib.sha512(sig[:32] + pubkey + message).digest(),
                       "little") % L
    # [s]B + [h](-A), one joint double-and-add from the top bit down.
    both = _add(BASE, neg_a)
    acc = IDENTITY
    for bit in range(max(s.bit_length(), h.bit_length()) - 1, -1, -1):
        acc = _add(acc, acc)
        sb, hb = (s >> bit) & 1, (h >> bit) & 1
        if sb and hb:
            acc = _add(acc, both)
        elif sb:
            acc = _add(acc, BASE)
        elif hb:
            acc = _add(acc, neg_a)
    return _encode(acc) == sig[:32]


def verify_many(jobs) -> list[bool]:
    """[(pubkey, message, sig), ...] -> verdicts (a worker pool's unit)."""
    return [verify(pk, m, s) for pk, m, s in jobs]
