"""JAX-batched BLS12-381 multi-point aggregation — the device tier behind
`scheme._sum_g1/_sum_g2` (the per-commit Σpk / Σsig of FastAggregateVerify).

Design mirrors the ed25519 limb kernels (ops/fe.py): small limbs in int32 —
TPUs have no native int64, so every 64-bit multiply is emulated — here
8-bit limbs (48 per Fp element, radix 2⁸) with CIOS Montgomery
multiplication.  Bound check for the interleaved accumulator: each of the
48 scan steps adds ≤ 2·255² ≈ 2¹⁷ per limb, so limbs stay < 48·2¹⁷ < 2²³,
comfortably inside int32.  Outputs are fully canonical (< P) after one
conditional subtract, which keeps the equality/infinity predicates of the
complete point-addition formulas exact.

Point addition is BRANCHLESS-complete: the Jacobian add and double are both
computed and the result is selected per lane (inf operands, P == Q, and
P == −Q all handled), so a batch never needs host-side case analysis.  The
reduction is a fixed-shape masked binary tree inside one jit — one compile
per power-of-two bucket, log₂(B) point-adds of wall depth.

The pure tier (`curve.py`) stays the differential oracle: tests pin
aggregate_g1/g2 against the sequential g1_add/g2_add fold on random batches.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from .fields import P

NL = 48  # limbs per Fp element
RADIX = 8
MASK = (1 << RADIX) - 1
MIN_BATCH = 8  # below this the pure-python fold wins (compile + transfer)

_R = 1 << (NL * RADIX)  # Montgomery R = 2^384
_R2 = (_R * _R) % P
_N0INV = (-pow(P, -1, 1 << RADIX)) & MASK  # -P⁻¹ mod 2⁸

_jax = None
_fns = {}  # (bucket, mesh, axis) -> (jitted g1 agg, jitted g2 agg)


def available() -> bool:
    global _jax
    if _jax is None:
        try:
            import jax

            _jax = jax
        except Exception:
            _jax = False
    return bool(_jax)


def _int_to_limbs(x: int):
    import numpy as np

    return np.frombuffer(x.to_bytes(NL, "little"), dtype=np.uint8).astype(np.int32)


def _limbs_to_int(a) -> int:
    import numpy as np

    return int.from_bytes(bytes(np.asarray(a, dtype=np.int32).astype(np.uint8)), "little")


def _build(bucket: int, mesh=None, batch_axis: str = "batch"):
    """Construct the jitted [bucket]-point G1 and G2 aggregators."""
    import jax

    from ... import ops  # noqa: F401 — places the compile cache before any compile
    import jax.numpy as jnp
    from jax import lax

    p_limbs = jnp.asarray(_int_to_limbs(P))

    # -- canonical Fp arithmetic, Montgomery domain ------------------------

    def _cond_sub_p(x):  # x: [NL] in [0, 2P) canonical limbs -> [0, P)
        d = x - p_limbs

        def bstep(c, di):
            t = di + c
            return t >> RADIX, t & MASK

        borrow, d_norm = lax.scan(bstep, jnp.int32(0), d)
        ge = borrow == 0  # no final borrow => x >= P
        return jnp.where(ge, d_norm, x)

    def _carry(x):  # x: [NL(+1)] nonneg redundant -> canonical limbs + top
        def cstep(c, xi):
            t = xi + c
            return t >> RADIX, t & MASK

        top, out = lax.scan(cstep, jnp.int32(0), x)
        return out, top

    def mont_mul(a, b):  # a, b: [NL] canonical -> [NL] canonical, = abR⁻¹
        def step(acc, ai):  # acc: [NL+1]
            acc = acc.at[:NL].add(ai * b)
            m = ((acc[0] & MASK) * _N0INV) & MASK
            acc = acc.at[:NL].add(m * p_limbs)
            acc = acc.at[1].add(acc[0] >> RADIX)
            acc = jnp.concatenate([acc[1:], jnp.zeros((1,), jnp.int32)])
            return acc, None

        acc, _ = lax.scan(step, jnp.zeros(NL + 1, jnp.int32), a)
        out, top = _carry(acc[:NL])
        # value < 2P < 2^383 and NL*RADIX = 384 bits: top limb is always 0
        return _cond_sub_p(out + top * 0)

    def fadd(a, b):
        s, top = _carry(a + b)  # a+b < 2P: top 0 after carry
        return _cond_sub_p(s + top * 0)

    def fsub(a, b):
        s, top = _carry(a - b + p_limbs)  # in (0, 2P); signed carry is exact
        return _cond_sub_p(s + top * 0)

    def fmuls(a, k: int):  # small scalar via repeated add (k in 2,3,4,8)
        out = a
        for _ in range(k - 1):
            out = fadd(out, a)
        return out

    def fzero_like():
        return jnp.zeros(NL, jnp.int32)

    def fis_zero(a):
        return jnp.all(a == 0)

    def feq(a, b):
        return jnp.all(a == b)

    # -- Fp2 (G2 coords): [2, NL] ------------------------------------------

    def f2_add(a, b):
        return jnp.stack([fadd(a[0], b[0]), fadd(a[1], b[1])])

    def f2_sub(a, b):
        return jnp.stack([fsub(a[0], b[0]), fsub(a[1], b[1])])

    def f2_mul(a, b):  # karatsuba, u² = -1
        t0 = mont_mul(a[0], b[0])
        t1 = mont_mul(a[1], b[1])
        t2 = mont_mul(fadd(a[0], a[1]), fadd(b[0], b[1]))
        return jnp.stack([fsub(t0, t1), fsub(fsub(t2, t0), t1)])

    def f2_sq(a):
        return f2_mul(a, a)

    def f2_muls(a, k: int):
        return jnp.stack([fmuls(a[0], k), fmuls(a[1], k)])

    def f2_is_zero(a):
        return jnp.all(a == 0)

    def f2_eq(a, b):
        return jnp.all(a == b)

    # -- generic complete Jacobian add over either field -------------------

    def _make_point_add(mul, sq, add_, sub_, muls, is_zero, eq):
        def pdouble(x, y, z):
            a = sq(x)
            b = sq(y)
            c = sq(b)
            d = muls(sub_(sub_(sq(add_(x, b)), a), c), 2)
            e = muls(a, 3)
            f = sq(e)
            x3 = sub_(f, muls(d, 2))
            y3 = sub_(mul(e, sub_(d, x3)), muls(c, 8))
            z3 = muls(mul(y, z), 2)
            return x3, y3, z3

        def padd(p, q):
            x1, y1, z1 = p
            x2, y2, z2 = q
            z1z1 = sq(z1)
            z2z2 = sq(z2)
            u1 = mul(x1, z2z2)
            u2 = mul(x2, z1z1)
            s1 = mul(mul(y1, z2), z2z2)
            s2 = mul(mul(y2, z1), z1z1)
            h = sub_(u2, u1)
            i = muls(sq(h), 4)
            j = mul(h, i)
            rr = muls(sub_(s2, s1), 2)
            v = mul(u1, i)
            x3 = sub_(sub_(sq(rr), j), muls(v, 2))
            y3 = sub_(mul(rr, sub_(v, x3)), muls(mul(s1, j), 2))
            z3 = muls(mul(mul(z1, z2), h), 2)

            dx, dy, dz = pdouble(x1, y1, z1)

            inf1 = is_zero(z1)
            inf2 = is_zero(z2)
            same_x = eq(u1, u2)
            same_y = eq(s1, s2)

            def sel(c, a, b):
                return jnp.where(c, a, b)

            # default: generic add; same point: double; opposite: inf;
            # either operand inf: the other
            ox = sel(same_x & same_y, dx, sel(same_x, fzero2(x3), x3))
            oy = sel(same_x & same_y, dy, sel(same_x, fzero2(y3), y3))
            oz = sel(same_x & same_y, dz, sel(same_x, fzero2(z3), z3))
            ox = sel(inf1, x2, sel(inf2, x1, ox))
            oy = sel(inf1, y2, sel(inf2, y1, oy))
            oz = sel(inf1, z2, sel(inf2, z1, oz))
            return ox, oy, oz

        def fzero2(like):
            return jnp.zeros_like(like)

        return padd

    g1_padd = _make_point_add(mont_mul, lambda a: mont_mul(a, a), fadd, fsub, fmuls, fis_zero, feq)
    g2_padd = _make_point_add(f2_mul, f2_sq, f2_add, f2_sub, f2_muls, f2_is_zero, f2_eq)

    # -- fixed-shape masked binary-tree reduction --------------------------

    steps = max(1, bucket.bit_length() - 1)  # log2(bucket)

    def _tree(pts, padd):
        # pts: [bucket, 3, ...]; identity = all-zero rows (Z = 0 => inf).
        # One fori_loop body — the point-add DAG traces ONCE, not per tree
        # level (measured: multi-minute XLA compiles when unrolled).
        idx = jnp.arange(bucket)
        vadd = jax.vmap(lambda a, b: jnp.stack(padd(tuple(a), tuple(b))))

        def level(s, cur):
            stride = jnp.int32(1) << s
            partner = jnp.roll(cur, -stride, axis=0)
            mask = (idx % (stride * 2)) == 0
            summed = vadd(cur, partner)
            return jnp.where(mask[(...,) + (None,) * (cur.ndim - 1)], summed, cur)

        pts = lax.fori_loop(0, steps, level, pts)
        return pts[0]

    if mesh is not None:
        # Sharded fold: points partitioned over the batch axis, output (the
        # tree root) replicated.  The roll-based tree reduction stays a
        # single jit — GSPMD lowers each level's roll to a collective
        # permute of boundary lanes, while the dominant cost (the vmapped
        # CIOS point-adds over all bucket lanes) splits across shards.
        from jax.sharding import NamedSharding, PartitionSpec as PS

        data = NamedSharding(mesh, PS(batch_axis))
        repl = NamedSharding(mesh, PS())
        g1 = jax.jit(lambda pts: _tree(pts, g1_padd),
                     in_shardings=(data,), out_shardings=repl)
        g2 = jax.jit(lambda pts: _tree(pts, g2_padd),
                     in_shardings=(data,), out_shardings=repl)
    else:
        g1 = jax.jit(lambda pts: _tree(pts, g1_padd))
        g2 = jax.jit(lambda pts: _tree(pts, g2_padd))
    return g1, g2


def _get_fns(bucket: int, mesh=None, batch_axis: str = "batch"):
    key = (bucket, mesh, batch_axis)
    if key not in _fns:
        _fns[key] = _build(bucket, mesh, batch_axis)
    return _fns[key]


def _to_mont(x: int) -> int:
    return (x * _R) % P


def _from_mont(x: int) -> int:
    return (x * pow(_R, P - 2, P)) % P


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _mesh_bucket(n: int, mesh):
    """Bucket + effective mesh for a fold of n points.  The masked tree
    needs power-of-two buckets, and a sharded batch axis must divide
    evenly — so the bucket grows to the mesh size for tiny folds, and a
    non-power-of-two mesh degrades to the single-device fold."""
    b = max(2, _bucket(n))
    if mesh is None:
        return b, None
    import numpy as np

    m = int(np.prod(list(mesh.shape.values())))
    if m < 2 or m & (m - 1):
        return b, None
    while b % m:
        b *= 2
    return b, mesh


def aggregate_g1(
    pts: Sequence[Tuple[int, int, int]], mesh=None
) -> Optional[Tuple[int, int, int]]:
    """Σ of Jacobian G1 points via the batched device tree; None on any
    failure (caller falls back to the pure fold)."""
    try:
        import numpy as np

        if not available() or not pts:
            return None
        b, mesh = _mesh_bucket(len(pts), mesh)
        rows = np.zeros((b, 3, NL), dtype=np.int32)
        for i, (x, y, z) in enumerate(pts):
            rows[i, 0] = _int_to_limbs(_to_mont(x % P))
            rows[i, 1] = _int_to_limbs(_to_mont(y % P))
            rows[i, 2] = _int_to_limbs(_to_mont(z % P))
        g1_fn, _ = _get_fns(b, mesh)
        out = np.asarray(g1_fn(rows))
        return (
            _from_mont(_limbs_to_int(out[0])),
            _from_mont(_limbs_to_int(out[1])),
            _from_mont(_limbs_to_int(out[2])),
        )
    except Exception:
        return None


def aggregate_g2(pts, mesh=None) -> Optional[tuple]:
    """Σ of Jacobian G2 points (Fp2 coords as int pairs)."""
    try:
        import numpy as np

        if not available() or not pts:
            return None
        b, mesh = _mesh_bucket(len(pts), mesh)
        rows = np.zeros((b, 3, 2, NL), dtype=np.int32)
        for i, (x, y, z) in enumerate(pts):
            for ci, coord in enumerate((x, y, z)):
                rows[i, ci, 0] = _int_to_limbs(_to_mont(coord[0] % P))
                rows[i, ci, 1] = _int_to_limbs(_to_mont(coord[1] % P))
        _, g2_fn = _get_fns(b, mesh)
        out = np.asarray(g2_fn(rows))
        return (
            (_from_mont(_limbs_to_int(out[0, 0])), _from_mont(_limbs_to_int(out[0, 1]))),
            (_from_mont(_limbs_to_int(out[1, 0])), _from_mont(_limbs_to_int(out[1, 1]))),
            (_from_mont(_limbs_to_int(out[2, 0])), _from_mont(_limbs_to_int(out[2, 1]))),
        )
    except Exception:
        return None
