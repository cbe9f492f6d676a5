"""Host crypto backend selection.

Every host-side primitive the framework needs (serial ed25519 sign/verify,
X25519 + HKDF + ChaCha20-Poly1305 for SecretConnection, secp256k1 ECDSA)
is routed through this module so the rest of the codebase never imports
`cryptography` directly.  Three tiers, best available wins per primitive:

1. the `cryptography` package (OpenSSL-backed) when importable;
2. the project's own C extension (csrc/sha512_batch.c — the same
   translation unit that accelerates batch host prep also carries a
   radix-2^51 ed25519 and a ChaCha20-Poly1305, ~0.1 ms/verify);
3. pure Python (`ed25519_math` + in-module ChaCha/X25519/ECDSA) so a
   toolchain-less, dependency-less host still runs — slowly but correctly.

The batched device path (crypto/batch_verifier.py) is unaffected: it only
needs host *prep*, not host verification.
"""

from __future__ import annotations

import hashlib
import hmac as _hmac
import logging
import os
import struct
from typing import Optional, Tuple

from . import ed25519_math as em

# --------------------------------------------------------------------------
# tier detection
# --------------------------------------------------------------------------

try:  # tier 1: the cryptography package
    from cryptography.exceptions import InvalidSignature as _InvalidSignature
    from cryptography.hazmat.primitives import hashes as _hashes
    from cryptography.hazmat.primitives.asymmetric import ec as _ec
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey as _LibEdPriv,
        Ed25519PublicKey as _LibEdPub,
    )
    from cryptography.hazmat.primitives.asymmetric.utils import (
        decode_dss_signature as _decode_dss,
        encode_dss_signature as _encode_dss,
    )
    from cryptography.hazmat.primitives.asymmetric.x25519 import (
        X25519PrivateKey as _LibXPriv,
        X25519PublicKey as _LibXPub,
    )
    from cryptography.hazmat.primitives.ciphers.aead import (
        ChaCha20Poly1305 as _LibChaCha,
    )
    from cryptography.hazmat.primitives.serialization import (
        Encoding as _Encoding,
        NoEncryption as _NoEncryption,
        PrivateFormat as _PrivateFormat,
        PublicFormat as _PublicFormat,
    )

    HAVE_CRYPTOGRAPHY = True
except ImportError:  # tiers 2/3
    HAVE_CRYPTOGRAPHY = False


def _clib():
    """The project C extension, or None.  Imported lazily: hostprep compiles
    on first use and this module is imported at package init."""
    from . import hostprep

    return hostprep._load_lib()


def active_tier() -> int:
    """Best available host-crypto tier for serial ed25519 work:
    1 = cryptography (OpenSSL), 2 = project C extension, 3 = pure python.
    Exported as the `tendermint_verify_backend_tier` gauge so a fleet
    operator can spot the node silently running the slow tier."""
    if HAVE_CRYPTOGRAPHY:
        return 1
    if _clib() is not None:
        return 2
    return 3


# --------------------------------------------------------------------------
# device mesh probe (the batch engine's scale axis)
# --------------------------------------------------------------------------


MESH_PROBE_FAILED = "mesh probe failed"


def resolve_mesh(
    mode: str = "auto", max_devices: int = 0, batch_axis: str = "batch"
) -> Tuple[object, int, str]:
    """Probe the visible accelerator devices and decide the verify engine's
    mesh.  Returns (mesh_or_None, shard_count, reason) — the same triple the
    node logs at start and exports as `tendermint_verify_shards`, so every
    engine number is attributable to the mesh that produced it.

    Modes ([tpu] mesh):
      "auto" — shard over all visible devices when more than one is
               attached, EXCEPT on the host-CPU platform: virtual CPU
               devices (xla_force_host_platform_device_count) emulate a
               mesh for tests/dryruns but lose on real workloads unless
               the host has cores to back them.  Setting mesh_devices > 1
               opts virtual-CPU meshes in (back-compat with the old
               explicit knob).
      "on"   — shard whenever >1 device is visible, any platform (the
               dryrun/smoke setting).
      "off"  — never shard.

    `max_devices` (tpu.mesh_devices) caps the shard count; 0 = all visible.
    The reason names the jax backend and device kind the engine will run
    on.  A probe failure degrades to single-device — a broken device plane
    must never stop the node (the host path still verifies) — but not
    quietly: it is logged at error level and the reason starts with
    MESH_PROBE_FAILED."""
    if mode == "off":
        return None, 1, "mesh off (config)"
    try:
        import jax
        import numpy as _np
        from jax.sharding import Mesh

        from .. import ops  # noqa: F401 — places the compile cache before any compile

        devs = jax.devices()
        on = f"{jax.default_backend()} {devs[0].device_kind}"
        cap = max_devices if max_devices > 0 else len(devs)
        cap = min(cap, len(devs))
        if cap <= 1:
            return None, 1, f"single device ({len(devs)} visible, {on})"
        if mode == "auto" and jax.default_backend() == "cpu" and max_devices <= 1:
            return None, 1, (
                f"{len(devs)} virtual cpu devices ignored by mesh=auto "
                "(set mesh=on or mesh_devices to shard)"
            )
        mesh = Mesh(_np.array(devs[:cap]), (batch_axis,))
        return mesh, cap, f"sharded over {cap}/{len(devs)} devices ({on})"
    except Exception as e:  # node start: the host path must still serve
        logging.getLogger(__name__).exception("verify engine: device mesh probe failed")
        return None, 1, f"{MESH_PROBE_FAILED}: {e!r}"


# --------------------------------------------------------------------------
# ed25519
# --------------------------------------------------------------------------


def ed25519_expand_seed(seed: bytes) -> Tuple[bytes, bytes]:
    """RFC 8032 §5.1.5: (clamped scalar LE32, prefix32)."""
    h = hashlib.sha512(seed).digest()
    a = bytearray(h[:32])
    a[0] &= 248
    a[31] &= 63
    a[31] |= 64
    return bytes(a), h[32:]


def ed25519_pub_from_seed(seed: bytes) -> bytes:
    if HAVE_CRYPTOGRAPHY:
        return (
            _LibEdPriv.from_private_bytes(seed)
            .public_key()
            .public_bytes(_Encoding.Raw, _PublicFormat.Raw)
        )
    lib = _clib()
    if lib is not None and hasattr(lib, "ed25519_pubkey"):
        import ctypes

        out = ctypes.create_string_buffer(32)
        lib.ed25519_pubkey(seed, out)
        return out.raw
    scalar, _ = ed25519_expand_seed(seed)
    a = int.from_bytes(scalar, "little")
    return em.compress(*em.to_affine(em.scalar_mult(a, em.BASE)))


def ed25519_sign(seed: bytes, pub: bytes, msg: bytes) -> bytes:
    if HAVE_CRYPTOGRAPHY:
        return _LibEdPriv.from_private_bytes(seed).sign(msg)
    lib = _clib()
    if lib is not None and hasattr(lib, "ed25519_sign"):
        import ctypes

        out = ctypes.create_string_buffer(64)
        lib.ed25519_sign(seed, pub, msg, len(msg), out)
        return out.raw
    scalar, prefix = ed25519_expand_seed(seed)
    return em.sign(scalar, prefix, pub, msg)


def ed25519_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """Cofactorless verify with canonical-S rejection (x/crypto parity).
    Callers already length-check; this re-checks defensively."""
    if len(sig) != 64 or len(pub) != 32:
        return False
    if not em.sc_minimal(sig[32:]):
        return False
    if HAVE_CRYPTOGRAPHY:
        try:
            _LibEdPub.from_public_bytes(pub).verify(sig, msg)
            return True
        except (_InvalidSignature, ValueError):
            return False
    lib = _clib()
    if lib is not None and hasattr(lib, "ed25519_verify"):
        return bool(lib.ed25519_verify(pub, msg, len(msg), sig))
    return em.verify(pub, msg, sig)


# --------------------------------------------------------------------------
# ChaCha20-Poly1305 (IETF, 12-byte nonce)
# --------------------------------------------------------------------------

_CHACHA_CONSTANTS = (0x61707865, 0x3320646E, 0x79622D32, 0x6B206574)


def _chacha20_block(key: bytes, counter: int, nonce: bytes) -> bytes:
    def rotl(v, n):
        return ((v << n) | (v >> (32 - n))) & 0xFFFFFFFF

    st = (
        list(_CHACHA_CONSTANTS)
        + list(struct.unpack("<8L", key))
        + [counter & 0xFFFFFFFF]
        + list(struct.unpack("<3L", nonce))
    )
    w = st[:]

    def qr(a, b, c, d):
        w[a] = (w[a] + w[b]) & 0xFFFFFFFF
        w[d] = rotl(w[d] ^ w[a], 16)
        w[c] = (w[c] + w[d]) & 0xFFFFFFFF
        w[b] = rotl(w[b] ^ w[c], 12)
        w[a] = (w[a] + w[b]) & 0xFFFFFFFF
        w[d] = rotl(w[d] ^ w[a], 8)
        w[c] = (w[c] + w[d]) & 0xFFFFFFFF
        w[b] = rotl(w[b] ^ w[c], 7)

    for _ in range(10):
        qr(0, 4, 8, 12)
        qr(1, 5, 9, 13)
        qr(2, 6, 10, 14)
        qr(3, 7, 11, 15)
        qr(0, 5, 10, 15)
        qr(1, 6, 11, 12)
        qr(2, 7, 8, 13)
        qr(3, 4, 9, 14)
    return struct.pack("<16L", *((w[i] + st[i]) & 0xFFFFFFFF for i in range(16)))


def _chacha20_xor(key: bytes, counter: int, nonce: bytes, data: bytes) -> bytes:
    out = bytearray(len(data))
    for i in range(0, len(data), 64):
        block = _chacha20_block(key, counter + i // 64, nonce)
        chunk = data[i : i + 64]
        out[i : i + len(chunk)] = bytes(a ^ b for a, b in zip(chunk, block))
    return bytes(out)


def _poly1305(key: bytes, msg: bytes) -> bytes:
    r = int.from_bytes(key[:16], "little") & 0x0FFFFFFC0FFFFFFC0FFFFFFC0FFFFFFF
    s = int.from_bytes(key[16:32], "little")
    p = (1 << 130) - 5
    acc = 0
    for i in range(0, len(msg), 16):
        block = msg[i : i + 16]
        n = int.from_bytes(block + b"\x01", "little")
        acc = (acc + n) * r % p
    return ((acc + s) & ((1 << 128) - 1)).to_bytes(16, "little")


def _pad16(b: bytes) -> bytes:
    return b"\x00" * (-len(b) % 16)


def _aead_tag(key: bytes, nonce: bytes, aad: bytes, ct: bytes) -> bytes:
    poly_key = _chacha20_block(key, 0, nonce)[:32]
    mac_data = (
        aad
        + _pad16(aad)
        + ct
        + _pad16(ct)
        + struct.pack("<QQ", len(aad), len(ct))
    )
    return _poly1305(poly_key, mac_data)


class AEADError(Exception):
    pass


def chacha20poly1305_seal(
    key: bytes, nonce: bytes, data: bytes, aad: bytes = b""
) -> bytes:
    """ciphertext || 16-byte tag (RFC 8439)."""
    if HAVE_CRYPTOGRAPHY:
        return _LibChaCha(key).encrypt(nonce, data, aad or None)
    lib = _clib()
    if lib is not None and hasattr(lib, "chacha20poly1305_seal"):
        import ctypes

        out = ctypes.create_string_buffer(len(data) + 16)
        lib.chacha20poly1305_seal(
            key, nonce, aad, len(aad), data, len(data), out
        )
        return out.raw
    ct = _chacha20_xor(key, 1, nonce, data)
    return ct + _aead_tag(key, nonce, aad, ct)


def chacha20poly1305_open(
    key: bytes, nonce: bytes, sealed: bytes, aad: bytes = b""
) -> bytes:
    """Decrypt or raise AEADError (constant-time tag compare)."""
    if HAVE_CRYPTOGRAPHY:
        from cryptography.exceptions import InvalidTag

        try:
            return _LibChaCha(key).decrypt(nonce, sealed, aad or None)
        except InvalidTag as e:
            raise AEADError("invalid tag") from e
    if len(sealed) < 16:
        raise AEADError("sealed frame too short")
    lib = _clib()
    if lib is not None and hasattr(lib, "chacha20poly1305_open"):
        import ctypes

        out = ctypes.create_string_buffer(max(len(sealed) - 16, 1))
        ok = lib.chacha20poly1305_open(
            key, nonce, aad, len(aad), sealed, len(sealed), out
        )
        if not ok:
            raise AEADError("invalid tag")
        return out.raw[: len(sealed) - 16]
    ct, tag = sealed[:-16], sealed[-16:]
    if not _hmac.compare_digest(_aead_tag(key, nonce, aad, ct), tag):
        raise AEADError("invalid tag")
    return _chacha20_xor(key, 1, nonce, ct)


# --------------------------------------------------------------------------
# X25519 (handshake only — once per connection, pure Python acceptable)
# --------------------------------------------------------------------------

_X25519_P = 2**255 - 19
_X25519_A24 = 121665


def _x25519_scalarmult(k_bytes: bytes, u_bytes: bytes) -> bytes:
    k = int.from_bytes(k_bytes, "little")
    k &= ~7
    k &= (1 << 254) - 1
    k |= 1 << 254
    u = int.from_bytes(u_bytes, "little") & ((1 << 255) - 1)
    p = _X25519_P
    x1, x2, z2, x3, z3 = u, 1, 0, u, 1
    swap = 0
    for t in reversed(range(255)):
        bit = (k >> t) & 1
        swap ^= bit
        if swap:
            x2, x3 = x3, x2
            z2, z3 = z3, z2
        swap = bit
        A = (x2 + z2) % p
        AA = A * A % p
        B = (x2 - z2) % p
        BB = B * B % p
        E = (AA - BB) % p
        C = (x3 + z3) % p
        D = (x3 - z3) % p
        DA = D * A % p
        CB = C * B % p
        x3 = (DA + CB) % p
        x3 = x3 * x3 % p
        z3 = (DA - CB) % p
        z3 = z3 * z3 % p * u % p
        x2 = AA * BB % p
        z2 = E * (AA + _X25519_A24 * E) % p
    if swap:
        x2, x3 = x3, x2
        z2, z3 = z3, z2
    return (x2 * pow(z2, p - 2, p) % p).to_bytes(32, "little")


_X25519_BASE = (9).to_bytes(32, "little")


def x25519_generate() -> Tuple[bytes, bytes]:
    """(private scalar bytes, public u-coordinate bytes)."""
    if HAVE_CRYPTOGRAPHY:
        priv = _LibXPriv.generate()
        return (
            priv.private_bytes(
                _Encoding.Raw, _PrivateFormat.Raw, _NoEncryption()
            ),
            priv.public_key().public_bytes(_Encoding.Raw, _PublicFormat.Raw),
        )
    sk = os.urandom(32)
    return sk, _x25519_scalarmult(sk, _X25519_BASE)


def x25519_shared(priv: bytes, peer_pub: bytes) -> bytes:
    if HAVE_CRYPTOGRAPHY:
        return _LibXPriv.from_private_bytes(priv).exchange(
            _LibXPub.from_public_bytes(peer_pub)
        )
    return _x25519_scalarmult(priv, peer_pub)


# --------------------------------------------------------------------------
# HKDF-SHA256
# --------------------------------------------------------------------------


def hkdf_sha256(ikm: bytes, length: int, info: bytes, salt: bytes = b"") -> bytes:
    if HAVE_CRYPTOGRAPHY:
        from cryptography.hazmat.primitives.kdf.hkdf import HKDF as _HKDF

        return _HKDF(
            algorithm=_hashes.SHA256(), length=length, salt=salt or None, info=info
        ).derive(ikm)
    prk = _hmac.new(salt or b"\x00" * 32, ikm, hashlib.sha256).digest()
    okm = b""
    t = b""
    i = 1
    while len(okm) < length:
        t = _hmac.new(prk, t + info + bytes([i]), hashlib.sha256).digest()
        okm += t
        i += 1
    return okm[:length]


# --------------------------------------------------------------------------
# secp256k1 ECDSA
# --------------------------------------------------------------------------

_SECP_P = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEFFFFFC2F
_SECP_N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
_SECP_GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
_SECP_GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8


def _secp_add(pt1, pt2):
    if pt1 is None:
        return pt2
    if pt2 is None:
        return pt1
    x1, y1 = pt1
    x2, y2 = pt2
    if x1 == x2 and (y1 + y2) % _SECP_P == 0:
        return None
    if pt1 == pt2:
        lam = (3 * x1 * x1) * pow(2 * y1, _SECP_P - 2, _SECP_P) % _SECP_P
    else:
        lam = (y2 - y1) * pow(x2 - x1, _SECP_P - 2, _SECP_P) % _SECP_P
    x3 = (lam * lam - x1 - x2) % _SECP_P
    return (x3, (lam * (x1 - x3) - y1) % _SECP_P)


def _secp_mul(k: int, pt):
    acc = None
    while k:
        if k & 1:
            acc = _secp_add(acc, pt)
        pt = _secp_add(pt, pt)
        k >>= 1
    return acc


def _secp_decompress(data: bytes) -> Optional[Tuple[int, int]]:
    if len(data) != 33 or data[0] not in (2, 3):
        return None
    x = int.from_bytes(data[1:], "big")
    if x >= _SECP_P:
        return None
    y2 = (x * x * x + 7) % _SECP_P
    y = pow(y2, (_SECP_P + 1) // 4, _SECP_P)
    if y * y % _SECP_P != y2:
        return None
    if (y & 1) != (data[0] & 1):
        y = _SECP_P - y
    return (x, y)


def ecdsa_compress(x: int, y: int) -> bytes:
    return bytes([2 | (y & 1)]) + x.to_bytes(32, "big")


def ecdsa_pub_from_priv(priv: bytes) -> bytes:
    """33-byte compressed pubkey."""
    if HAVE_CRYPTOGRAPHY:
        handle = _ec.derive_private_key(int.from_bytes(priv, "big"), _ec.SECP256K1())
        return handle.public_key().public_bytes(
            _Encoding.X962, _PublicFormat.CompressedPoint
        )
    d = int.from_bytes(priv, "big")
    pt = _secp_mul(d, (_SECP_GX, _SECP_GY))
    return ecdsa_compress(*pt)


def ecdsa_generate() -> bytes:
    while True:
        d = int.from_bytes(os.urandom(32), "big")
        if 0 < d < _SECP_N:
            return d.to_bytes(32, "big")


def _rfc6979_k(priv: bytes, digest: bytes) -> int:
    """Deterministic nonce (RFC 6979, SHA-256)."""
    v = b"\x01" * 32
    k = b"\x00" * 32
    k = _hmac.new(k, v + b"\x00" + priv + digest, hashlib.sha256).digest()
    v = _hmac.new(k, v, hashlib.sha256).digest()
    k = _hmac.new(k, v + b"\x01" + priv + digest, hashlib.sha256).digest()
    v = _hmac.new(k, v, hashlib.sha256).digest()
    while True:
        v = _hmac.new(k, v, hashlib.sha256).digest()
        cand = int.from_bytes(v, "big")
        if 0 < cand < _SECP_N:
            return cand
        k = _hmac.new(k, v + b"\x00", hashlib.sha256).digest()
        v = _hmac.new(k, v, hashlib.sha256).digest()


def ecdsa_sign(priv: bytes, msg: bytes) -> Tuple[int, int]:
    """SHA-256 ECDSA, low-S normalized; returns (r, s)."""
    if HAVE_CRYPTOGRAPHY:
        handle = _ec.derive_private_key(int.from_bytes(priv, "big"), _ec.SECP256K1())
        der = handle.sign(msg, _ec.ECDSA(_hashes.SHA256()))
        r, s = _decode_dss(der)
        if s > _SECP_N // 2:
            s = _SECP_N - s
        return r, s
    digest = hashlib.sha256(msg).digest()
    z = int.from_bytes(digest, "big")
    d = int.from_bytes(priv, "big")
    while True:
        k = _rfc6979_k(priv, digest)
        pt = _secp_mul(k, (_SECP_GX, _SECP_GY))
        r = pt[0] % _SECP_N
        if r == 0:
            continue
        s = pow(k, _SECP_N - 2, _SECP_N) * (z + r * d) % _SECP_N
        if s == 0:
            continue
        if s > _SECP_N // 2:
            s = _SECP_N - s
        return r, s


def ecdsa_verify(pub33: bytes, msg: bytes, r: int, s: int) -> bool:
    if not (0 < r < _SECP_N and 0 < s < _SECP_N):
        return False
    if HAVE_CRYPTOGRAPHY:
        try:
            handle = _ec.EllipticCurvePublicKey.from_encoded_point(
                _ec.SECP256K1(), pub33
            )
            handle.verify(_encode_dss(r, s), msg, _ec.ECDSA(_hashes.SHA256()))
            return True
        except Exception:
            return False
    pt = _secp_decompress(pub33)
    if pt is None:
        return False
    z = int.from_bytes(hashlib.sha256(msg).digest(), "big")
    w = pow(s, _SECP_N - 2, _SECP_N)
    u1 = z * w % _SECP_N
    u2 = r * w % _SECP_N
    res = _secp_add(_secp_mul(u1, (_SECP_GX, _SECP_GY)), _secp_mul(u2, pt))
    if res is None:
        return False
    return res[0] % _SECP_N == r
