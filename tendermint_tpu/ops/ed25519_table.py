"""Tabulated ed25519 verification: zero-doubling ladder via per-validator
window tables.

The Straus ladder (ops/ed25519_pallas.py) spends 2/3 of its point ops on
the 256 shared doublings required because A varies per signature.  But the
framework's hot verifier runs against a *stable validator set* — so the
doublings can be hoisted into a one-time per-validator precomputation:

    table[v, w, d] = d · 16^w · (−A_v)   (w = 0..63, d = 0..15)

Verification of signature i then needs NO doublings at all:

    [h](−A) + [s]B = Σ_w table[idx_i, w, h_digit_w] + Σ_w base[w, s_digit_w]

i.e. a sum of 128 gathered points, 128 point-adds instead of 384 ladder
ops — ~2.4x less VPU work for the steady-state commit-verification path
(BASELINE config #5: 10k-validator commit replay).  The gathers ride XLA;
the adds + inversion + canonical compare run in one Pallas kernel with a
VMEM accumulator (grid = batch tiles × window chunks, k-loop pattern).

MEASURED (one v5e chip, PR 21, PERF.md): 79 ms per 10k-committee dispatch
vs 23 ms for the VMEM-resident Straus ladder (ops/ed25519_pallas.py), and
slower at 256, 1024 and 4096 validators too.  The VPU saving is real but
the 128 random 160 B row gathers per signature plus the
[B,128,4,20]→[128,4,20,B] relayout (1.5 GB of temporaries at 10k) are
HBM-bound and dominate.  Engaged only where PubkeyTable's run-time profile
measures a win; making the gather sequential (sorting signatures by
validator, fusing the gather into the pallas grid) is the open avenue if
this path is to win.

Tables store canonical limbs as int16 ([V, 64, 16, 4, 20] = 160 KB per
validator, 1.6 GB for 10k) and are built on-device by a jitted scan over
slices of BUILD_CHUNK validators — once per validator-set change,
amortized over every subsequent commit at that height range.

Reference contrast: crypto/ed25519/ed25519.go:151 verifies one signature
at a time with a fresh double-and-add each call; nothing is amortized.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..crypto import ed25519_math as em
from . import curve, fe
from .ed25519_pallas import _RollFieldOps as _FO, _row

N = fe.N_LIMBS
N_WINDOWS = 64
N_DIGITS = 16
WBLK = 16  # windows per pallas grid step (128 / WBLK accumulation steps)


# ---------------------------------------------------------------------------
# table build (device, one-time per validator set)
# ---------------------------------------------------------------------------


@jax.jit
def _build_tables_jit(neg_a: jnp.ndarray) -> jnp.ndarray:
    """[V, 4, 20] int32 extended −A  ->  [V*64*16, 4, 20] int16 canonical
    window tables (flat for gather)."""
    na = neg_a.astype(jnp.int32).transpose(1, 2, 0)  # [4, 20, V]
    v = na.shape[-1]
    p0 = (na[0], na[1], na[2], na[3])
    two_d = fe.broadcast_const(fe.from_int(2 * em.D % em.P), 1)
    identity = tuple(
        jnp.broadcast_to(c, (N, v)).astype(jnp.int32)
        for c in (fe.from_int(0), fe.from_int(1), fe.from_int(1), fe.from_int(0))
    )

    # lax.scan over windows with the running point 16^w·(−A) as carry
    def w_step(p, _):
        # multiples 1..15 of p via an inner scan (14 adds)
        def d_step(m, _):
            nxt = curve.point_add(fe, m, p, two_d)
            return nxt, jnp.stack(nxt)

        _, mults = lax.scan(d_step, p, None, length=N_DIGITS - 2)  # [14, 4, 20, V]
        entries = jnp.concatenate(
            [jnp.stack(identity)[None], jnp.stack(p)[None], mults], axis=0
        )  # [16, 4, 20, V]
        # canonicalize every coordinate so limbs fit int16 and compare
        # equal regardless of the projective representative's limb split
        flat = entries.reshape(N_DIGITS * 4, N, v).transpose(1, 0, 2).reshape(N, -1)
        canon = curve.canonical(flat)
        entries16 = (
            canon.reshape(N, N_DIGITS * 4, v)
            .transpose(1, 0, 2)
            .reshape(N_DIGITS, 4, N, v)
            .astype(jnp.int16)
        )
        nxt = p
        for _ in range(4):
            nxt = curve.point_double(fe, nxt)
        return nxt, entries16

    _, tab = lax.scan(w_step, p0, None, length=N_WINDOWS)  # [64, 16, 4, 20, V]
    return tab.transpose(4, 0, 1, 2, 3).reshape(v * N_WINDOWS * N_DIGITS, 4, N)


# Validators per build dispatch.  The build's XLA temporaries are ~9x its
# output (compiler memory analysis on a v5e, libtpu 0.0.34: 14.8 GB of
# temp for the 1.64 GB tables of 10,000 validators — all of a 16 GB chip,
# and invisible to memory_stats' peak counter), so a committee is built a
# slice at a time: ~1.5 GB of temp whatever its size.
BUILD_CHUNK = 1024


def build_window_tables(neg_a_rows) -> jnp.ndarray:
    """Public entry: [V, 4, 20] (any int dtype) -> flat device tables.
    Above BUILD_CHUNK validators the rows are padded with the identity
    point to whole chunks (one compiled shape) and the tables carry the
    padding's rows at the end, which no validator index reaches."""
    rows = jnp.asarray(neg_a_rows)
    v = rows.shape[0]
    if v <= BUILD_CHUNK:
        return _build_tables_jit(rows)
    pad = -v % BUILD_CHUNK
    if pad:
        identity = jnp.zeros((pad, 4, N), rows.dtype).at[:, 1:3, 0].set(1)
        rows = jnp.concatenate([rows, identity])
    return jnp.concatenate(
        [_build_tables_jit(rows[i : i + BUILD_CHUNK]) for i in range(0, v, BUILD_CHUNK)]
    )


def _build_base_windows() -> np.ndarray:
    """[64*16, 4, 20] int32: d·16^w·B in extended coords with Z=1 —
    compile-time constant (host bigint math, runs once per process)."""
    rows = np.zeros((N_WINDOWS * N_DIGITS, 4, N), dtype=np.int32)
    one = fe.from_int(1)[:, 0]
    for w in range(N_WINDOWS):
        base_w = em.scalar_mult(pow(16, w, em.L), em.BASE)
        for d in range(N_DIGITS):
            if d == 0:
                rows[w * N_DIGITS, 1] = one
                rows[w * N_DIGITS, 2] = one
                continue
            x, y = em.to_affine(em.scalar_mult(d, base_w))
            rows[w * N_DIGITS + d, 0] = fe.from_int(x)[:, 0]
            rows[w * N_DIGITS + d, 1] = fe.from_int(y)[:, 0]
            rows[w * N_DIGITS + d, 2] = one
            rows[w * N_DIGITS + d, 3] = fe.from_int(x * y % em.P)[:, 0]
    return rows


@functools.lru_cache(maxsize=1)
def base_windows() -> np.ndarray:
    return _build_base_windows()


# ---------------------------------------------------------------------------
# the summation kernel
# ---------------------------------------------------------------------------


def _identity_block(t):
    one = jnp.broadcast_to(jnp.where(_row(N) == 0, 1, 0), (N, t)).astype(jnp.int32)
    zero = jnp.zeros((N, t), jnp.int32)
    return jnp.stack([zero, one, one, zero])  # [4, 20, T]


def _sum_kernel(n_wsteps, consts_ref, pts_ref, ry_ref, rsign_ref, out_ref, acc_ref):
    w = pl.program_id(1)
    t = pts_ref.shape[-1]
    two_d = consts_ref[0][:, None]

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = _identity_block(t)

    a = acc_ref[...]
    acc = (a[0], a[1], a[2], a[3])
    for i in range(WBLK):
        q = (pts_ref[i, 0], pts_ref[i, 1], pts_ref[i, 2], pts_ref[i, 3])
        acc = curve.point_add(_FO, acc, q, two_d)
    acc_ref[...] = jnp.stack(acc)

    @pl.when(w == n_wsteps - 1)
    def _finalize():
        zinv = curve.invert(_FO, acc[2])
        x = curve.canonical(_FO.mul(acc[0], zinv))
        y = curve.canonical(_FO.mul(acc[1], zinv))
        ok_y = jnp.sum(jnp.where(y == ry_ref[...], 1, 0), axis=0) == N
        ok_sign = (x[0] & 1) == rsign_ref[0]
        out_ref[...] = (ok_y & ok_sign).astype(jnp.int32)[None]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _sum_verify(
    pts: jnp.ndarray,  # [W, 4, 20, B] int32 — all gathered points
    ry: jnp.ndarray,  # [20, B]
    rsign: jnp.ndarray,  # [1, B]
    tile: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    w_total, _, _, b = pts.shape
    assert b % tile == 0 and w_total % WBLK == 0, (b, tile, w_total)
    n_wsteps = w_total // WBLK
    consts = jnp.asarray(fe.from_int(2 * em.D % em.P).T)  # [1, 20]

    ok = pl.pallas_call(
        functools.partial(_sum_kernel, n_wsteps),
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.int32),
        grid=(b // tile, n_wsteps),
        in_specs=[
            pl.BlockSpec((1, N), lambda i, w: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (WBLK, 4, N, tile), lambda i, w: (w, 0, 0, i), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec((N, tile), lambda i, w: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), lambda i, w: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i, w: (0, i), memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((4, N, tile), jnp.int32)],
        interpret=interpret,
    )(consts, pts, ry, rsign)
    return ok[0].astype(bool)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def verify_tabulated(
    tables: jnp.ndarray,  # [V*64*16, 4, 20] int16 (build_window_tables)
    idx: jnp.ndarray,  # [B] int32 validator row per signature
    h_digits: jnp.ndarray,  # [B, 64] 4-bit digits of h, MSB first
    s_digits: jnp.ndarray,  # [B, 64] 4-bit digits of s, MSB first
    r_y_raw: jnp.ndarray,  # [B, 20]
    r_sign: jnp.ndarray,  # [B]
    tile: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    b = idx.shape[0]
    warange = jnp.arange(N_WINDOWS, dtype=jnp.int32)
    # digits arrive MSB-first (ladder order); table windows are LSB-first
    hd = h_digits.astype(jnp.int32)[:, ::-1]
    sd = s_digits.astype(jnp.int32)[:, ::-1]

    gidx_a = (idx.astype(jnp.int32)[:, None] * N_WINDOWS + warange) * N_DIGITS + hd
    pts_a = jnp.take(tables, gidx_a.reshape(-1), axis=0).astype(jnp.int32)  # [B*64,4,20]
    base = jnp.asarray(base_windows())
    gidx_b = warange * N_DIGITS + sd
    pts_b = jnp.take(base, gidx_b.reshape(-1), axis=0)  # [B*64, 4, 20]

    pts = jnp.concatenate(
        [pts_a.reshape(b, N_WINDOWS, 4, N), pts_b.reshape(b, N_WINDOWS, 4, N)], axis=1
    )  # [B, 128, 4, 20]
    pts = pts.transpose(1, 2, 3, 0)  # [128, 4, 20, B]
    ry = r_y_raw.astype(jnp.int32).T
    rs = r_sign.astype(jnp.int32)[None]
    return _sum_verify(pts, ry, rs, tile=tile, interpret=interpret)
