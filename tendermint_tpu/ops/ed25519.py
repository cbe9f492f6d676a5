"""Batched cofactorless ed25519 verification kernel — windowed Straus (XLA).

The portable TPU/CPU replacement for the reference's per-signature
VerifyBytes hot loop (crypto/ed25519/ed25519.go:151; serial call sites
types/vote_set.go:201, types/validator_set.go:641-668, lite2/verifier.go:32).
On TPU backends the Pallas variant (ops/ed25519_pallas.py) is preferred;
this XLA version is the CPU/test and multi-chip (shard-by-batch) path.
Both share the curve layer in ops/curve.py — only the field carry
plumbing differs.

Per signature the kernel computes R' = [s]B + [h](−A) with a 4-bit
windowed Straus ladder (64 iterations of 4 shared doublings + 2 table
additions) and compares R's canonical encoding against the signature's
raw R limbs — byte-compare semantics identical to the host path, so
consensus can never fork on edge-case signatures.  The fixed base B uses
a compile-time table of d·B in madd form; the per-signature d·(−A) table
(d=0..15) is built per batch and selected branch-free.

Host-side prep (crypto/batch_verifier.py): pubkey decompression (cached
per validator set), SHA-512 h = H(R‖A‖M), reduction mod L, 4-bit digit
extraction.  Device-side: all curve arithmetic, vectorized over the batch.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..crypto import ed25519_math as em
from . import curve, fe

N_WINDOWS = 64  # 4-bit windows covering full 256-bit scalars

TWO_D = fe.from_int(2 * em.D % em.P)

# identity in extended coordinates (0, 1, 1, 0) as [20, 1] constants
IDENTITY = (fe.from_int(0), fe.from_int(1), fe.from_int(1), fe.from_int(0))


def _build_base_table() -> np.ndarray:
    """[16, 3, 20] int32: d·B for d=0..15 in madd form (y−x, y+x, 2d·x·y).
    Entry 0 is the identity's madd form (1, 1, 0), which makes point_madd
    return the same projective point (scaled by 4)."""
    rows = np.zeros((16, 3, fe.N_LIMBS), dtype=np.int32)
    rows[0, 0] = fe.from_int(1)[:, 0]
    rows[0, 1] = fe.from_int(1)[:, 0]
    for d in range(1, 16):
        x, y = em.to_affine(em.scalar_mult(d, em.BASE))
        rows[d, 0] = fe.from_int((y - x) % em.P)[:, 0]
        rows[d, 1] = fe.from_int((y + x) % em.P)[:, 0]
        rows[d, 2] = fe.from_int(2 * em.D * x % em.P * y % em.P)[:, 0]
    return rows


BASE_TABLE = _build_base_table()  # numpy; becomes an XLA constant under jit


def point_add(p, q):
    return curve.point_add(fe, p, q, TWO_D)


def point_double(p):
    return curve.point_double(fe, p)


def verify_prepared(
    neg_a: jnp.ndarray,  # [B, 4, 20] int extended coords of -A
    h_digits: jnp.ndarray,  # [B, 64] 4-bit digits of h, MSB first
    s_digits: jnp.ndarray,  # [B, 64] 4-bit digits of s, MSB first
    r_y_raw: jnp.ndarray,  # [B, 20] raw (unreduced) y limbs from sig R bytes
    r_sign: jnp.ndarray,  # [B] x-parity bit from sig R bytes
) -> jnp.ndarray:
    """Returns [B] bool: does [s]B + [h](−A) encode to the signature's R."""
    batch = neg_a.shape[0]

    na = neg_a.astype(jnp.int32).transpose(1, 2, 0)  # [4, 20, B]
    a1 = (na[0], na[1], na[2], na[3])
    ident = tuple(fe.broadcast_const(c, batch) for c in IDENTITY)
    a_tab = curve.neg_a_table(fe, a1, ident, TWO_D)
    hd = h_digits.astype(jnp.int32).T  # [64, B]: window digits, MSB first
    sd = s_digits.astype(jnp.int32).T
    base_tab = jnp.asarray(BASE_TABLE)

    def body(i, acc):
        for _ in range(4):
            acc = curve.point_double(fe, acc)
        h_i = lax.dynamic_index_in_dim(hd, i, 0, keepdims=False)  # [B]
        acc = curve.point_add(fe, acc, curve.select_point(a_tab, h_i), TWO_D)
        s_i = lax.dynamic_index_in_dim(sd, i, 0, keepdims=False)
        q = jnp.take(base_tab, s_i, axis=0).transpose(1, 2, 0)  # [3, 20, B]
        return curve.point_madd(fe, acc, (q[0], q[1], q[2]))

    acc = lax.fori_loop(0, N_WINDOWS, body, ident)

    # affine + canonical encode
    zinv = curve.invert(fe, acc[2])
    x = curve.canonical(fe.mul(acc[0], zinv))
    y = curve.canonical(fe.mul(acc[1], zinv))

    # byte-compare semantics: raw sig limbs must equal the canonical
    # encoding exactly (non-canonical sig R encodings fail automatically)
    ok_y = fe.eq(y, r_y_raw.astype(jnp.int32).T)
    ok_sign = (x[0] & 1) == r_sign.astype(jnp.int32)
    return ok_y & ok_sign


def expand_digits(packed_le: jnp.ndarray) -> jnp.ndarray:
    """[B, 32] little-endian scalar bytes -> [B, 64] 4-bit window digits,
    MSB first — the device-side twin of batch_verifier._msb_digits.

    Kept as a kernel-level op so dispatch paths can ship 32 packed bytes
    per scalar instead of 64 digit bytes: halving the h/s payload costs
    two shifts and an interleave, fused into the verify kernel's prologue
    by XLA."""
    lo = packed_le & 15
    hi = packed_le >> 4
    dig = jnp.stack([lo, hi], axis=-1).reshape(packed_le.shape[0], 64)
    return dig[:, ::-1]


def verify_prepared_packed(
    neg_a: jnp.ndarray,  # [B, 4, 20] int extended coords of -A
    h_le: jnp.ndarray,  # [B, 32] little-endian bytes of h (mod L)
    s_le: jnp.ndarray,  # [B, 32] little-endian bytes of s
    r_y_raw: jnp.ndarray,  # [B, 20] raw (unreduced) y limbs from sig R bytes
    r_sign: jnp.ndarray,  # [B] x-parity bit from sig R bytes
) -> jnp.ndarray:
    """verify_prepared with in-kernel digit expansion (32 B/scalar wire
    format).  Bit-identical to expanding on the host: digits are 4-bit, so
    pack→expand round-trips exactly."""
    return verify_prepared(
        neg_a, expand_digits(h_le), expand_digits(s_le), r_y_raw, r_sign
    )


verify_prepared_jit = jax.jit(verify_prepared)
