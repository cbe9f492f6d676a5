"""TPU batch verifier: the framework's north-star engine.

Replaces the reference's serial per-signature verification
(crypto/ed25519/ed25519.go:151 called from types/vote_set.go:201,
types/validator_set.go:641-668, lite2/verifier.go:32,
blockchain/v0/reactor.go:216 replay, mempool CheckTx) with one vmapped
curve kernel over an HBM-resident pubkey table.

Split of labor:
  host   — pubkey decompression (cached; table built once per validator
           set), SHA-512 h = H(R‖A‖M), reduction mod L, structural
           prefilters (length, canonical S).  These are ~1% of the CPU cost
           of a verify; the expensive double-scalar multiplication is 99%.
  device — [s]B + [h](−A) for the whole batch (ops/ed25519.py).

Batches are padded to power-of-two buckets so XLA compiles a handful of
shapes once; with a `jax.sharding.Mesh` the batch axis is split across
chips under `shard_map` (data-parallel over signatures — the system's scale
axis per SURVEY.md §5 long-context note): every chip runs the same inner
verify on its rows, no collective.  A batch too small to give every chip a
whole kernel tile runs on one device of the mesh.
"""

from __future__ import annotations

import asyncio
import hashlib
import logging
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..libs import tracing
from ..libs.metrics import VerifyMetrics
from ..libs.service import Service
from . import batch as batch_hook
from . import ed25519_math as em

logger = logging.getLogger(__name__)

_MIN_BUCKET = 16


def _bucket_size(n: int, multiple_of: int = 1) -> int:
    b = _MIN_BUCKET
    while b < n:
        b *= 2
    if b % multiple_of:
        b = ((b + multiple_of - 1) // multiple_of) * multiple_of
    return b


# ---------------------------------------------------------------------------
# host-side preparation
# ---------------------------------------------------------------------------

_N_LIMBS = 20
_LIMB_BITS = 13

# Bounded LRU: pubkeys are attacker-suppliable (mempool/evidence paths), so
# the cache must not grow without limit.  64k entries of [4, 20] int16
# (~160 B payload each) ≈ 10 MB worst case plus dict overhead.
_DECOMPRESS_CACHE_MAX = 65536
import collections as _collections

_decompress_cache: "_collections.OrderedDict[bytes, Optional[np.ndarray]]" = (
    _collections.OrderedDict()
)
import threading as _threading

# The cache is reached from the event-loop thread (verify_commit / lite2 via
# the installed hook) AND the flush executor thread concurrently; an
# unlocked check-then-act on the OrderedDict can KeyError at the eviction cap.
_decompress_lock = _threading.Lock()


def _neg_a_limbs(pubkey: bytes) -> Optional[np.ndarray]:
    """Decompress pubkey and return extended coords of −A as [4, 20] int32
    13-bit limbs; None for invalid encodings.  LRU-cached — validator
    pubkeys are hot across heights."""
    with _decompress_lock:
        if pubkey in _decompress_cache:
            _decompress_cache.move_to_end(pubkey)
            return _decompress_cache[pubkey]
    aff = em.decompress(pubkey)
    if aff is None:
        limbs = None
    else:
        x, y = aff
        nx = (em.P - x) % em.P
        ext = (nx, y, 1, nx * y % em.P)
        limbs = np.zeros((4, _N_LIMBS), dtype=np.int16)
        for c in range(4):
            v = ext[c]
            for i in range(_N_LIMBS):
                limbs[c, i] = (v >> (_LIMB_BITS * i)) & ((1 << _LIMB_BITS) - 1)
    with _decompress_lock:
        _decompress_cache[pubkey] = limbs
        if len(_decompress_cache) > _DECOMPRESS_CACHE_MAX:
            _decompress_cache.popitem(last=False)
    return limbs


def _msb_digits(values_le: np.ndarray) -> np.ndarray:
    """[B, 32] little-endian scalar byte rows -> [B, 64] 4-bit window
    digits, most-significant digit first (the kernel's ladder order)."""
    dig = np.empty((values_le.shape[0], 64), dtype=np.uint8)
    dig[:, 0::2] = values_le & 15  # little-endian digit 2k
    dig[:, 1::2] = values_le >> 4  # little-endian digit 2k+1
    return dig[:, ::-1]


def _pack_digits(digits: np.ndarray) -> np.ndarray:
    """[B, 64] 4-bit MSB-first window digits -> [B, 32] little-endian scalar
    bytes — inverse of _msb_digits, exact (digits are 4-bit).  The fused
    indexed dispatch ships this packed form and expands on-device
    (ops/ed25519.expand_digits): half the h/s transfer per signature."""
    rev = digits[:, ::-1]
    return (rev[:, 0::2] | (rev[:, 1::2].astype(np.uint8) << 4)).astype(np.uint8)


def _r_limbs_and_sign(r_bytes: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """[B, 32] little-endian R rows -> raw y limbs [B, 20] + sign bit [B]."""
    from . import hostprep

    return hostprep.limbs_from_le_bytes(r_bytes), hostprep.sign_bits(r_bytes)


def _scalar_rows(
    items: Sequence[Optional[Tuple[bytes, bytes, bytes]]],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Shared per-signature host prep: SHA-512 h, scalar s, raw R limbs,
    canonical-S / length prefilters.  `items[i]` is (pubkey, msg, sig) or
    None when the caller already knows entry i is invalid.  Returns
    (h_digits, s_digits, r_y_raw, r_sign, valid).

    Fast path: one fused, threaded C pass (hostprep.prep_scalar_rows)
    straight from bytes to kernel-ready arrays — hash, mod-L reduce, digit
    extraction, limb packing and the canonical-S prefilter never surface
    as intermediate numpy arrays.  The numpy pipeline below remains as the
    no-toolchain fallback and the differential-test reference."""
    from . import hostprep

    fused = hostprep.prep_scalar_rows(items)
    if fused is not None:
        return fused

    n = len(items)
    valid = np.zeros(n, dtype=bool)
    zeros32 = bytes(32)
    s_parts: list = [zeros32] * n
    r_parts: list = [zeros32] * n
    hash_parts: list = []
    hash_pos: list = []
    for i, item in enumerate(items):
        if item is None:
            continue
        pk, msg, sig = item
        if len(sig) != 64 or len(pk) != 32:
            continue
        s_parts[i] = sig[32:]
        r_parts[i] = sig[:32]
        hash_parts.append(sig[:32] + pk + msg)
        hash_pos.append(i)
        valid[i] = True
    # one frombuffer per column instead of 3n row-wise assignments
    s_le = np.frombuffer(b"".join(s_parts), dtype=np.uint8).reshape(n, 32)
    r_le = np.frombuffer(b"".join(r_parts), dtype=np.uint8).reshape(n, 32)
    # canonical-S prefilter, vectorized (was a per-item bigint compare)
    valid &= hostprep.sc_minimal_rows(s_le)
    # h = SHA-512(R‖A‖M) mod L: one fused C pass (hash + Barrett reduce)
    h_le = np.zeros((n, 32), dtype=np.uint8)
    if hash_parts:
        h_le[hash_pos] = hostprep.sha512_mod_l(hash_parts)
    r_y_raw, r_sign = _r_limbs_and_sign(r_le)
    return _msb_digits(h_le), _msb_digits(s_le), r_y_raw, r_sign, valid


def _pad_scalar_rows(b: int, h_digits, s_digits, r_y, r_sign):
    """Pad the per-signature arrays up to bucket size b."""
    n = h_digits.shape[0]
    pad = b - n
    if pad <= 0:
        return h_digits, s_digits, r_y, r_sign
    return (
        np.concatenate([h_digits, np.zeros((pad, 64), dtype=np.uint8)]),
        np.concatenate([s_digits, np.zeros((pad, 64), dtype=np.uint8)]),
        np.concatenate([r_y, np.zeros((pad, _N_LIMBS), dtype=np.int16)]),
        np.concatenate([r_sign, np.zeros(pad, dtype=np.uint8)]),
    )


def prepare_batch(
    pubkeys: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Host prep: returns (neg_a [B,4,20], h_digits [B,64], s_digits [B,64],
    r_y_raw [B,20], r_sign [B], valid [B])."""
    n = len(sigs)
    neg_a = np.zeros((n, 4, _N_LIMBS), dtype=np.int16)
    neg_a[:, 1, :1] = 1  # identity placeholder (0,1,1,0): y=z=1
    neg_a[:, 2, :1] = 1
    items: list = [None] * n
    for i, (pk, msg, sig) in enumerate(zip(pubkeys, msgs, sigs)):
        if len(pk) != 32:
            continue
        limbs = _neg_a_limbs(pk)
        if limbs is None:
            continue
        neg_a[i] = limbs
        items[i] = (pk, msg, sig)
    h_digits, s_digits, r_y_raw, r_sign, valid = _scalar_rows(items)
    return neg_a, h_digits, s_digits, r_y_raw, r_sign, valid


# ---------------------------------------------------------------------------
# the verifier
# ---------------------------------------------------------------------------


_PALLAS_TILE = 512  # best-measured batch tile (sublane 20 x lane 512 blocks)
_CHUNK = 2048  # double-buffer chunk for large single-shot indexed batches

# Process-wide jit wrappers, shared across BatchVerifier/PubkeyTable
# instances.  jax.jit memoizes traces per WRAPPER object: a per-instance
# wrapper re-traces (and re-lowers) every bucket shape for every new
# verifier — seconds per shape on a small host even when the persistent
# compile cache hits, and tests/nodes create many verifiers.
_shared_jit_lock = _threading.Lock()
_shared_jit: Dict = {}


def _shared(key, build):
    with _shared_jit_lock:
        fn = _shared_jit.get(key)
        if fn is None:
            fn = _shared_jit[key] = build()
    return fn


def _shared_pallas_fn(tile: int, interpret: bool = False):
    """Process-wide Pallas verify entry point.  Must be shared for the
    same reason as the jit wrappers — and because _shared_fused_jit keys
    by id(inner): a per-instance functools.partial would mint a fresh
    never-evicted fused-jit cache entry (and a full re-trace) for every
    PubkeyTable on a TPU backend.  `interpret` is for the CPU tests."""

    def build():
        import functools

        from ..ops.ed25519_pallas import verify_prepared_pallas

        return functools.partial(verify_prepared_pallas, tile=tile, interpret=interpret)

    return _shared(("pallas", tile, interpret), build)


def _mesh_jit(fn, mesh, batch_axis: str, replicated: int, donate=()):
    """`fn` once per chip of the mesh under `shard_map`, jitted under fn's
    own name: its first `replicated` arguments whole on every chip, the five
    per-signature arrays after them and the verdicts split over the batch
    axis.  Each chip works on its own rows and no collective runs — which is
    also how a Pallas custom call, which GSPMD cannot partition, runs on a
    mesh.  The jit's shardings say the same, so host arrays go straight to
    their chips."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    specs = (P(),) * replicated + (P(batch_axis),) * 5
    return jax.jit(
        jax.shard_map(
            fn, mesh=mesh, in_specs=specs, out_specs=P(batch_axis), check_vma=False
        ),
        in_shardings=tuple(NamedSharding(mesh, spec) for spec in specs),
        out_shardings=NamedSharding(mesh, P(batch_axis)),
        donate_argnums=donate,
    )


def _shared_verify_jit(inner, mesh=None, batch_axis: str = "batch"):
    """The flat dispatch: `inner` itself (a jitted verify) on one device,
    `inner` per shard on a mesh."""
    if mesh is None:
        return inner
    return _shared(
        ("flat", id(inner), mesh, batch_axis), lambda: _mesh_jit(inner, mesh, batch_axis, 0)
    )


def _shared_fused_jit(inner, mesh=None, batch_axis: str = "batch", donate: bool = False):
    """Fused gather+verify wrapper, one per inner verify (which is itself
    process-wide) — same per-instance re-trace trap as above.

    Wire format: h/s arrive as PACKED 32-byte little-endian scalars and are
    expanded to window digits on-device (ops/ed25519.expand_digits) — half
    the per-signature scalar transfer, exactly round-trippable.

    With a mesh the wrapper is itself the sharded dispatch (_mesh_jit):
    pubkey rows replicated (the HBM-resident table lives on every chip),
    per-signature arrays and verdicts split over the batch axis, the gather
    shard-local.  This is the jit the warmup path compiles, so the first
    real sharded dispatch never eats the compile.

    `donate` is the double-buffered single-shot path's per-chunk variant:
    the per-signature arrays DONATED — every chunk ships fresh host-prepped
    buffers, so the device reuses their allocation instead of growing the
    arena one chunk at a time.  Donation is NOT safe on the plain wrapper
    (bench and steady-state callers legitimately re-dispatch the same
    device arrays).  CPU backends ignore donation (and warn per call), so it
    is requested only off-CPU."""

    def build():
        import jax
        import jax.numpy as jnp

        from ..ops import ed25519_kernel

        def run(rows, idx, h_le, s_le, ry, rs):
            return inner(
                jnp.take(rows, idx, axis=0),
                ed25519_kernel.expand_digits(h_le),
                ed25519_kernel.expand_digits(s_le),
                ry,
                rs,
            )

        donated = (1, 2, 3, 4, 5) if donate and jax.default_backend() != "cpu" else ()
        if mesh is not None:
            return _mesh_jit(run, mesh, batch_axis, 1, donated)
        return jax.jit(run, donate_argnums=donated)

    return _shared(("fused", id(inner), mesh, batch_axis, donate), build)


class BatchVerifier:
    """Batched ed25519 verification, jitted per bucket shape.

    On a TPU backend the Pallas kernel (ops/ed25519_pallas.py) runs the
    whole ladder VMEM-resident — ~4x the fused-XLA kernel, ~20x the serial
    host path; elsewhere (the CPU tests, `mesh = on` on another platform)
    the portable XLA kernel (ops/ed25519.py) does.  With `mesh` (multi-chip)
    the same inner verify runs per shard under `shard_map`, inputs and
    verdicts split over the batch axis; a batch whose bucket would not give
    every chip a whole kernel tile runs on one device of the mesh instead.
    """

    def __init__(
        self,
        mesh=None,
        batch_axis: str = "batch",
        min_device_batch: int = 1,
        metrics: Optional[VerifyMetrics] = None,
        recorder=None,
        chunk_size: int = 0,
        chunk_depth: int = 2,
    ):
        self.mesh = mesh
        self.batch_axis = batch_axis
        # How many devices the batch axis is partitioned over (1 = no mesh).
        # Stamped on every verify.* recorder event so bench/telescope/trace
        # output can attribute which mesh produced a number.
        self.shards = (
            1 if mesh is None else int(np.prod(list(mesh.shape.values())))
        )
        # Double-buffered single-shot knobs ([tpu] chunk_size / chunk_depth):
        # chunk_size 0 = module default _CHUNK; chunk_depth bounds in-flight
        # donated chunks (host memory stays O(depth·chunk), and the host
        # can never race more than `depth` dispatches ahead of the device).
        self.chunk_size = chunk_size
        self.chunk_depth = chunk_depth
        # observability: nop by default; the node passes its provider's
        # VerifyMetrics and its FlightRecorder.  PubkeyTable / TableCache /
        # AsyncBatchVerifier all report through their verifier's pair, so
        # wiring the one engine instance instruments the whole pipeline.
        self.metrics = metrics if metrics is not None else VerifyMetrics()
        self.recorder = recorder if recorder is not None else tracing.NOP
        # Batches below this ride the serial host path: a tiny batch's
        # device dispatch (one round trip plus a whole padded bucket of
        # ladder work) costs more than ~0.15 ms/sig host verification.  1 =
        # always device (bench/tests); nodes set it from config
        # (tpu.min_device_batch).
        self.min_device_batch = min_device_batch
        self._pallas = None  # resolved lazily: backend known only at first use
        self._interpret = False  # the ladder under the Pallas interpreter (CPU tests only)
        # Cold-start handling.  When warmup mode is on, verify() serves any
        # bucket shape whose XLA compile hasn't landed yet from the serial
        # host path while a background thread compiles it — a cold or
        # restarted node never stalls consensus on a compile (the reference
        # never stalls: crypto/ed25519/ed25519.go:151 is always ready).
        # When off (bench, direct use), compiles run inline as before.
        self._warmup_mode = False
        self._ready_buckets: set = set()
        self._compiling_buckets: set = set()
        self._failed_buckets: set = set()
        self._warm_lock = _threading.Lock()
        # host<->device dispatch RTT probe (measured at install; drives the
        # chunked-single-shot auto-selection).  None until probed.
        self.rtt_probe: Optional[Dict[str, float]] = None

    def probe_dispatch_rtt(self, samples: int = 7) -> Dict[str, float]:
        """Measure what one extra device dispatch costs vs what one chunk
        of host prep saves, and decide whether double-buffered chunking
        pays (see PubkeyTable.chunked_single_shot).

        - dispatch_rtt_ms: min round-trip of a minimal jitted dispatch +
          result fetch (what every extra chunk dispatch costs), over every
          chip of the mesh when there is one.
        - prep_ms_per_chunk: host prep time for one chunk of signatures
          (what overlap can hide per extra dispatch).

        Chunking is selected iff dispatch_rtt_ms < prep_ms_per_chunk.
        Cached after the first call."""
        if self.rtt_probe is not None:
            return self.rtt_probe
        import time as _time

        import jax
        import jax.numpy as jnp

        from .. import ops  # noqa: F401 — places the compile cache before any compile

        tiny = jax.jit(lambda x: x + 1)
        x = jnp.zeros(8 * self.shards, jnp.int32)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            x = jax.device_put(x, NamedSharding(self.mesh, P(self.batch_axis)))
        tiny(x).block_until_ready()  # compile outside the timed loop
        rtts = []
        for _ in range(samples):
            t0 = _time.perf_counter()
            tiny(x).block_until_ready()
            rtts.append(_time.perf_counter() - t0)
        rtt_ms = min(rtts) * 1000
        # host prep rate from a synthetic mini-batch (sign-bytes-sized msgs)
        probe_n = 512
        items = [
            (bytes(32), b"\x08\x02\x11" + bytes(100), bytes(64))
            for _ in range(probe_n)
        ]
        _scalar_rows(items)  # warm allocators / C lib load
        t0 = _time.perf_counter()
        _scalar_rows(items)
        prep_per_sig_ms = (_time.perf_counter() - t0) * 1000 / probe_n
        prep_ms_per_chunk = prep_per_sig_ms * self.effective_chunk()
        self.rtt_probe = {
            "dispatch_rtt_ms": rtt_ms,
            "prep_ms_per_chunk": prep_ms_per_chunk,
            "chunked_selected": float(rtt_ms < prep_ms_per_chunk),
        }
        self.recorder.record(
            "verify.chunked",
            selected=bool(rtt_ms < prep_ms_per_chunk),
            ok=True,
            rtt_ms=round(rtt_ms, 4),
            prep_ms=round(prep_ms_per_chunk, 4),
            shards=self.shards,
        )
        return self.rtt_probe

    def effective_chunk(self) -> int:
        """Rows of one chunk of the double-buffered path.  The configured
        size (or the module default) is what ONE chip takes per chunk, in
        whole kernel tiles: a mesh's chunk is that many rows for every chip,
        so an extra dispatch buys each chip as much work as it buys a chip
        alone (2,048 rows over four chips would be one 512-row tile each)."""
        tile = self._tile()
        per_chip = -(-(self.chunk_size or _CHUNK) // tile) * tile
        return per_chip * self.shards

    def chunked_auto(self) -> bool:
        """True when the RTT probe says chunked single-shot overlap pays.
        A probe that raises keeps the monolithic path, and says so: an
        error log line and a `verify.chunked` event with ok=False."""
        try:
            return bool(self.probe_dispatch_rtt()["chunked_selected"])
        except Exception as exc:  # runs on the install thread: must not escape
            logger.exception("verify engine: dispatch RTT probe failed")
            self.recorder.record(
                "verify.chunked", selected=False, ok=False, error=repr(exc),
                shards=self.shards,
            )
            return False

    def _compile_bucket(self, b: int) -> None:
        neg_a = np.zeros((b, 4, _N_LIMBS), dtype=np.int16)
        neg_a[:, 1, :1] = 1
        neg_a[:, 2, :1] = 1
        h = np.zeros((b, 64), dtype=np.uint8)
        s = np.zeros((b, 64), dtype=np.uint8)
        r_y = np.zeros((b, _N_LIMBS), dtype=np.int16)
        r_s = np.zeros(b, dtype=np.uint8)
        np.asarray(self._jitted(self._shards_for(b))(neg_a, h, s, r_y, r_s))

    def _bucket_ready(self, b: int) -> bool:
        """True when bucket b may run on-device without an inline compile.
        Otherwise kicks off (at most one) background compile for b and
        returns False so the caller falls back to the host path.  A failed
        compile leaves the bucket on the host path rather than routing
        traffic to a known-broken device — loudly: an error log line and a
        `verify.bucket_compile` event with ok=False and the error text."""
        if not self._warmup_mode:
            return True
        with self._warm_lock:
            if b in self._ready_buckets:
                return True
            if b in self._compiling_buckets or b in self._failed_buckets:
                return False
            self._compiling_buckets.add(b)

        def _compile():
            import time as _time

            error = None
            t0 = _time.perf_counter()
            try:
                self._compile_bucket(b)
            except Exception as exc:  # warmup thread: must not escape
                error = repr(exc)
                logger.exception(
                    "verify engine: bucket %d compile failed; batches of "
                    "this size stay on the host path", b,
                )
            with self._warm_lock:
                self._compiling_buckets.discard(b)
                (self._failed_buckets if error else self._ready_buckets).add(b)
            self.metrics.bucket_compiles.inc()
            self.recorder.record(
                "verify.bucket_compile",
                bucket=b,
                ms=round((_time.perf_counter() - t0) * 1000, 3),
                ok=error is None,
                error=error,
                shards=self.shards,
            )

        # non-daemon: a daemon thread killed mid-XLA-compile at interpreter
        # exit aborts the whole process from C++ ("terminate called");
        # joining at exit costs at most one compile
        _threading.Thread(target=_compile, daemon=False, name=f"bv-warmup-{b}").start()
        return False

    # min_device_batch values past this can never be reached by a real
    # batch: the engine is in permanent host-tier routing (e.g. a CPU-only
    # box running a committee-scale rig) and pre-compiling device buckets
    # would burn cores on kernels that will never dispatch.
    _NEVER_DEVICE = 1 << 16

    def start_warmup(self) -> "BatchVerifier":
        """Enable cold-start host fallback and pre-compile the smallest
        bucket that can actually dispatch — the first shape at or above
        min_device_batch (verify() routes smaller batches to the host
        tier, so warming below it is wasted compile).  With
        min_device_batch effectively infinite, no bucket is compiled at
        all: at 100 co-located nodes the eager per-node warmup compile
        was measured stealing both cores for minutes."""
        self._warmup_mode = True
        if self.min_device_batch < self._NEVER_DEVICE:
            self._bucket_ready(self._bucket(max(1, self.min_device_batch)))
        return self

    def rewarm(self, n: int) -> None:
        """Re-probe the warmup bucket for an expected batch size of `n`
        signatures (a validator-set size change): start_warmup compiled
        the bucket for min_device_batch, but a grown set's commit batch
        lands in a LARGER bucket that was never compiled — without this
        the first post-rotation commit eats a live XLA compile behind a
        node that believes itself warm.  No-op when warmup mode is off,
        when n routes to the host tier, or when the bucket is already
        ready/compiling."""
        if not self._warmup_mode or self.min_device_batch >= self._NEVER_DEVICE:
            return
        if n < self.min_device_batch:
            return
        self._bucket_ready(self._bucket(n))

    def _use_pallas(self) -> bool:
        if self._pallas is None:
            import jax

            self._pallas = jax.default_backend() == "tpu"
        return self._pallas

    def _tile(self) -> int:
        """Rows the inner verify takes at a time: the ladder's batch tile;
        the XLA kernel takes any number."""
        return _PALLAS_TILE if self._use_pallas() else 1

    def _inner(self):
        """The verify that runs on each device (process-wide objects): the
        Pallas ladder, or XLA Straus where there is no TPU."""
        if self._use_pallas():
            return _shared_pallas_fn(_PALLAS_TILE, self._interpret)
        from ..ops import ed25519_kernel

        return ed25519_kernel.verify_prepared_jit

    def _mesh_for(self, shards: Optional[int] = None):
        """The mesh a dispatch split over `shards` devices runs on (the
        default: every device there is); None for one device."""
        return self.mesh if (shards or self.shards) > 1 else None

    def _jitted(self, shards: Optional[int] = None):
        """The flat dispatch over the whole mesh (the default) or, with
        `shards` 1, on one device."""
        return _shared_verify_jit(self._inner(), self._mesh_for(shards), self.batch_axis)

    def _bucket(self, n: int) -> int:
        """Rows dispatched for a batch of n: powers of two up to 2048, then
        multiples of 1024 — bounds padding waste at large batches (10k pads
        to 10240, not 16384; +60% device time and transfer otherwise) while
        the shapes stay few and compile-cached — never less than one kernel
        tile.  On a mesh a bucket that gives every shard a tile is rounded up
        so that every shard's rows are whole tiles; a smaller one is left as
        one device takes it (_shards_for)."""
        b = max(_bucket_size(n), self._tile()) if n <= 2048 else -(-n // 1024) * 1024
        step = self.shards * self._tile()
        if b >= step and b % step:
            b = -(-b // step) * step
        return b

    def _shards_for(self, bucket: int) -> int:
        """How many devices a bucket's rows are split over: the whole mesh,
        or one device of it for a bucket under a kernel tile per chip (a
        166-signature commit in a 512-row bucket must not pad to 2,048 rows
        to fill four chips)."""
        return self.shards if bucket >= self.shards * self._tile() else 1

    @staticmethod
    def shard_fill(n: int, rows: int, shards: int) -> List[int]:
        """Useful rows in each shard when the first n of `rows` rows are
        signatures and the rows are split evenly over `shards`: the padding
        lands on the last shards.  With n over `rows` (the chunked path: a
        dispatch of several chunks of `rows`), summed over the chunks."""
        per = rows // shards
        full, rest = divmod(n, rows)
        return [full * per + max(0, min(per, rest - i * per)) for i in range(shards)]

    def _dispatch_span(self, n: int, bucket: int, path: str) -> "tracing.Span":
        """The `verify.dispatch` span of one engine call.  Its laps tile the
        call: `host_prep_ms` + `device_ms` is its wall time on every path,
        but for `rows_ms` on the table paths (the row list built from the
        caller's indices, which these two never covered)."""
        return self.recorder.begin(
            "verify.dispatch", n=n, bucket=bucket, path=path, shards=self.shards,
            host_prep_ms=0.0, device_ms=0.0,
        )

    def _device_span(self, n: int, bucket: int, path: str, shards: int) -> "tracing.Span":
        """A device path's span: besides _dispatch_span's fields, how many
        devices THIS dispatch is split over (`shards`: the mesh, or 1 with
        `device`, the one it was routed to), which inner verify the jit wraps
        (`kernel`: ladder or straus) and the useful rows in each shard
        (`shard_n`; on the chunked path, where `bucket` is one chunk, summed
        over the chunks)."""
        span = self._dispatch_span(n, bucket, path)
        span.set(
            shards=shards,
            kernel="ladder" if self._use_pallas() else "straus",
            shard_n=self.shard_fill(n, bucket, shards),
        )
        if shards == 1 and self.mesh is not None:
            span.set(device=int(self.mesh.devices.flat[0].id))
        return span

    def _put(self, span: "tracing.Span", shards: int, arrays):
        """Start the per-signature arrays' transfer to the mesh, split over
        the batch axis (SNIPPETS pjit guidance: correctly pre-partitioned
        inputs skip the resharding step), or to the one device.  Async; the
        time the host spends here is `put_ms`, a part of `launch_ms`."""
        import time as _time

        import jax

        from jax.sharding import NamedSharding, PartitionSpec as P

        t0 = _time.perf_counter()
        where = None if shards == 1 else NamedSharding(self.mesh, P(self.batch_axis))
        dev = jax.device_put(list(arrays), where)
        span.fields["put_ms"] = (
            span.fields.get("put_ms", 0.0) + (_time.perf_counter() - t0) * 1e3
        )
        return dev

    def _end_device_span(self, span: "tracing.Span") -> None:
        """Close a device path's span: `device_ms` is what followed host
        prep (pack, transfer and launch, kernel and copy back), and the
        Prometheus histogram takes the same reading."""
        f = span.fields
        f["device_ms"] = f["pack_ms"] + f["launch_ms"] + f["fetch_ms"]
        self.metrics.device_seconds.observe(f["device_ms"] / 1e3)
        span.end()

    def verify(
        self, pubkeys: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
    ) -> List[bool]:
        n = len(sigs)
        if n == 0:
            return []
        self.metrics.batch_size.observe(n)
        b = 0 if n < self.min_device_batch else self._bucket(n)
        if b == 0 or not self._bucket_ready(b):
            with self._dispatch_span(n, b, "host" if b == 0 else "host-cold") as span:
                out = batch_hook.host_batch_verify(pubkeys, msgs, sigs)
                span.lap("device_ms")
            return out
        shards = self._shards_for(b)
        span = self._device_span(n, b, "device", shards)
        neg_a, h_digits, s_digits, r_y, r_sign, valid = prepare_batch(pubkeys, msgs, sigs)
        self.metrics.host_prep_seconds.observe(span.lap("host_prep_ms") / 1e3)
        if not valid.any():
            span.drop()
            return [False] * n
        if b > n:
            neg_a = np.concatenate([neg_a, np.tile(neg_a[-1:], (b - n, 1, 1))])
        h_digits, s_digits, r_y, r_sign = _pad_scalar_rows(b, h_digits, s_digits, r_y, r_sign)
        span.lap("pack_ms")
        args = (neg_a, h_digits, s_digits, r_y, r_sign)
        if shards > 1:
            args = self._put(span, shards, args)
        dev_ok = self._jitted(shards)(*args)
        span.lap("launch_ms")
        out = list(np.logical_and(np.asarray(dev_ok)[:n], valid))
        span.lap("fetch_ms")
        self._end_device_span(span)
        return out

    def install(self) -> "BatchVerifier":
        """Become the process-wide batch-verify hook used by
        ValidatorSet.verify_commit* and friends.  Kicks off the dispatch
        RTT probe in the background so the chunked-single-shot decision is
        ready (and reported) before the first large batch arrives."""
        batch_hook.set_verifier(self.verify)
        _threading.Thread(
            target=self.chunked_auto, daemon=False, name="bv-rtt-probe"
        ).start()
        return self


class PubkeyTable:
    """HBM-resident decompressed validator pubkey table, keyed by validator
    index — commits verify by gathering rows on-device (the BASELINE.json
    north star).  Rebuilt only on validator-set changes."""

    def __init__(
        self,
        pubkeys: Sequence[bytes],
        verifier: Optional[BatchVerifier] = None,
    ):
        import jax.numpy as jnp

        self.verifier = verifier or BatchVerifier()
        n = len(pubkeys)
        rows = np.zeros((max(n, 1), 4, _N_LIMBS), dtype=np.int32)
        rows[:, 1, :1] = 1
        rows[:, 2, :1] = 1
        self.row_valid = np.zeros(max(n, 1), dtype=bool)
        self.pubkeys = [bytes(pk) for pk in pubkeys]
        for i, pk in enumerate(pubkeys):
            limbs = _neg_a_limbs(bytes(pk))
            if limbs is not None:
                rows[i] = limbs
                self.row_valid[i] = True
        if self.verifier.mesh is not None:
            # HBM-resident and REPLICATED: every chip holds the full table,
            # so the fused gather stays shard-local (no collectives) and the
            # sharded jit's replicated in_sharding is already satisfied —
            # zero per-dispatch table movement.
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.neg_a_rows = jax.device_put(
                jnp.asarray(rows), NamedSharding(self.verifier.mesh, P())
            )
            # the first device's copy, for a batch routed to one device
            self._rows_one = self.neg_a_rows.addressable_shards[0].data
        else:
            self.neg_a_rows = self._rows_one = jnp.asarray(rows)  # device-resident
        # Double-buffered chunking overlaps host prep with device compute
        # (saves ~prep time), but each extra dispatch pays the host<->device
        # round trip, which can exceed the saving.  None = auto: decided by
        # the verifier's install-time RTT probe (chunked iff one dispatch
        # RTT < one chunk of host prep).  True/False still force it either
        # way.
        self.chunked_single_shot: Optional[bool] = None

    def __len__(self) -> int:
        return len(self.pubkeys)

    def _fused(self, shards: Optional[int] = None, donate: bool = False):
        """One jitted dispatch: on-device gather of the pubkey rows fused
        with the verify kernel — a second dispatch would pay the host↔device
        round trip twice.  Takes PACKED h/s (32 B/scalar, _pack_digits);
        expansion happens in-kernel.  Over the whole mesh (the default: rows
        replicated, per-signature arrays split over the batch axis) or, with
        `shards` 1, on one device."""
        v = self.verifier
        return _shared_fused_jit(v._inner(), v._mesh_for(shards), v.batch_axis, donate)

    def _chunked(self):
        """Per-chunk donated-buffer variant of _fused (see _shared_fused_jit)."""
        return self._fused(donate=True)

    def verify_indexed(
        self, idxs: Sequence[int], msgs: Sequence[bytes], sigs: Sequence[bytes]
    ) -> List[bool]:
        """Verify msgs[i]/sigs[i] against table row idxs[i]."""
        n = len(sigs)
        if n == 0:
            return []
        pk_count = len(self.pubkeys)
        self.verifier.metrics.batch_size.observe(n)
        if n < self.verifier.min_device_batch:
            return batch_hook.host_batch_verify(
                [
                    self.pubkeys[i] if 0 <= i < pk_count else b""
                    for i in (int(i) for i in idxs)
                ],
                msgs,
                sigs,
            )
        # the one-time decision (the RTT probe: seconds) comes before the
        # span, which times a dispatch and nothing else
        cs = self.verifier.effective_chunk()
        chunked = n >= 2 * cs and bool(
            self.verifier.chunked_auto()
            if self.chunked_single_shot is None
            else self.chunked_single_shot
        )
        if chunked:
            path, b = "chunked", cs
        else:
            path, b = "indexed", self.verifier._bucket(n)
        shards = self.verifier._shards_for(b)  # a chunk gives every chip whole tiles
        span = self.verifier._device_span(n, b, path, shards)

        idx_arr = np.asarray(idxs, dtype=np.int32)
        # Host prep for everything except pubkey limbs (gathered on device);
        # entries with bad indices are marked invalid up front.
        items: list = [None] * n
        idx_list = idx_arr.tolist()
        for i, (idx, msg, sig) in enumerate(zip(idx_list, msgs, sigs)):
            if 0 <= idx < pk_count and self.row_valid[idx]:
                items[i] = (self.pubkeys[idx], msg, sig)
        span.lap("rows_ms")

        if chunked:
            # Double-buffered single-shot: device dispatch (and the
            # pre-partitioned device_put) is async, so prepping chunk k+1
            # on the host while the device runs chunk k hides most of the
            # host prep inside device time — single-shot latency ≈
            # prep(chunk 1) + device(total) instead of prep(total) +
            # device(total).  Prep and device time interleave by design;
            # every stage's laps are summed over the chunks.
            fn = self._chunked()
            depth = max(1, self.verifier.chunk_depth)
            pending: "_collections.deque" = _collections.deque()
            out: List[bool] = []

            def _collect():
                dev_ok, valid_c, cnt = pending.popleft()
                out.extend(
                    np.logical_and(np.asarray(dev_ok)[:cnt], valid_c).tolist()
                )

            for start in range(0, n, cs):
                end = min(start + cs, n)
                h, s, ry, rs, valid_c = _scalar_rows(items[start:end])
                span.lap("host_prep_ms")
                cnt = end - start
                h, s, ry, rs = _pad_scalar_rows(cs, h, s, ry, rs)
                idx_c = idx_arr[start:end]
                if cnt < cs:
                    idx_c = np.concatenate([idx_c, np.zeros(cs - cnt, np.int32)])
                idx_c = np.clip(idx_c, 0, pk_count - 1)
                h, s = _pack_digits(h), _pack_digits(s)
                span.lap("pack_ms")
                # Bound in-flight chunks: fetching the oldest result here
                # blocks until the device drains it, so donated buffers in
                # flight stay at O(depth·chunk) and the host never races
                # more than chunk_depth dispatches ahead of the device.
                while len(pending) >= depth:
                    _collect()
                span.lap("fetch_ms")
                # the transfer of chunk k+1 overlaps device verify of chunk
                # k; the jax Arrays are what the donated chunk jit consumes
                dev = self.verifier._put(span, shards, (idx_c, h, s, ry, rs))
                pending.append((fn(self.neg_a_rows, *dev), valid_c, cnt))
                span.lap("launch_ms")
            while pending:
                _collect()
            span.lap("fetch_ms")
            self.verifier.metrics.host_prep_seconds.observe(span.fields["host_prep_ms"] / 1e3)
            self.verifier._end_device_span(span)
            return out

        h_digits, s_digits, r_y, r_sign, valid = _scalar_rows(items)
        self.verifier.metrics.host_prep_seconds.observe(span.lap("host_prep_ms") / 1e3)
        if not valid.any():
            span.drop()
            return [False] * n

        h_digits, s_digits, r_y, r_sign = _pad_scalar_rows(b, h_digits, s_digits, r_y, r_sign)
        if b > n:
            idx_arr = np.concatenate([idx_arr, np.zeros(b - n, dtype=np.int32)])
        idx_arr = np.clip(idx_arr, 0, pk_count - 1)
        fused = self._fused(shards)
        h_digits, s_digits = _pack_digits(h_digits), _pack_digits(s_digits)
        span.lap("pack_ms")
        args = (idx_arr, h_digits, s_digits, r_y, r_sign)
        if shards > 1:
            dev_ok = fused(self.neg_a_rows, *self.verifier._put(span, shards, args))
        else:
            dev_ok = fused(self._rows_one, *args)
        span.lap("launch_ms")
        out = list(np.logical_and(np.asarray(dev_ok)[:n], valid))
        span.lap("fetch_ms")
        self.verifier._end_device_span(span)
        return out


class TableCache:
    """Per-validator-set device tables for indexed commit verification.

    verify_commit knows (validator-set hash, row indices); routing through
    this cache lets the steady-state commit path gather pubkey rows
    on-device instead of shipping pubkeys every call.  Keyed by the set
    hash; small LRU — consensus touches at most current + last validator
    sets, lite2 a few more.

    Installed process-wide via `install()` (crypto.batch.set_indexed_verifier);
    returns None (declining, caller falls back to the flat batch) while the
    engine is cold or when a set exceeds the table budget.
    """

    def __init__(self, verifier: Optional[BatchVerifier] = None, max_sets: int = 4):
        self.verifier = verifier or BatchVerifier()
        self.max_sets = max_sets
        self._tables: "_collections.OrderedDict[bytes, PubkeyTable]" = (
            _collections.OrderedDict()
        )
        self._building: set = set()
        self._lock = _threading.Lock()

    def table_for(self, set_key: bytes, pubkeys: Sequence[bytes]) -> PubkeyTable:
        """Get-or-build synchronously (bench / direct use)."""
        with self._lock:
            tab = self._tables.get(set_key)
            if tab is not None:
                self._tables.move_to_end(set_key)
                return tab
        return self._publish(set_key, self._new_table(pubkeys))

    def _new_table(self, pubkeys: Sequence[bytes]) -> PubkeyTable:
        return PubkeyTable(pubkeys, verifier=self.verifier)

    def _publish(self, set_key: bytes, tab: PubkeyTable) -> PubkeyTable:
        with self._lock:
            self._tables[set_key] = tab
            if len(self._tables) > self.max_sets:
                self._tables.popitem(last=False)
        return tab

    def verify_indexed(
        self,
        set_key: bytes,
        pubkeys: Sequence[bytes],
        idxs: Sequence[int],
        msgs: Sequence[bytes],
        sigs: Sequence[bytes],
    ) -> Optional[List[bool]]:
        with self._lock:
            tab = self._tables.get(set_key)
            if tab is not None:
                self._tables.move_to_end(set_key)
        if tab is not None:
            self.verifier.metrics.table_cache_hits.inc()
            self.verifier.recorder.record("verify.table", hit=True, n=len(sigs))
            return tab.verify_indexed(idxs, msgs, sigs)
        self.verifier.metrics.table_cache_misses.inc()
        self.verifier.recorder.record("verify.table", hit=False, n=len(sigs))
        if not self.verifier._warmup_mode:
            return self.table_for(set_key, self._rows(pubkeys)).verify_indexed(idxs, msgs, sigs)
        # Node mode: building (decompress + device table compile, seconds at
        # 10k validators) must not stall the event loop — build in the
        # background once and decline meanwhile; the flat batch path (with
        # its own cold fallback) serves until the table is ready.
        with self._lock:
            if set_key in self._building:
                return None
            self._building.add(set_key)
        self._build_in_background(
            "verify.table_build",
            set_key,
            [bytes(pk) for pk in self._rows(pubkeys)],
            n_warm=max(len(sigs), 1),
        )
        return None

    def _build_in_background(
        self, event: str, set_key: bytes, pubkeys: List[bytes], n_warm: int
    ) -> None:
        """Build the set's device table on a thread and warm the verify
        pipeline at the shape an n_warm-signature commit will use BEFORE
        the table becomes visible to verify_indexed — otherwise the first
        post-build verify_commit jit-compiles inline on the consensus event
        loop, the very stall the decline-while-cold dance exists to avoid.
        The caller has put set_key in _building.  Success or failure, the
        outcome lands in the recorder as `event` (ok, error); a failure is
        also logged, and the set stays on the flat path until the next
        miss retries the build."""
        import time as _time

        t0 = _time.perf_counter()

        def _build():
            error = None
            try:
                tab = self._new_table(pubkeys)
                tab.verify_indexed(
                    [i % max(len(pubkeys), 1) for i in range(n_warm)],
                    [b"warmup"] * n_warm,
                    [bytes(64)] * n_warm,
                )
                self._publish(set_key, tab)
            except Exception as exc:  # build thread: must not escape
                error = repr(exc)
                logger.exception(
                    "verify engine: device table build failed for a "
                    "%d-validator set; its commits stay on the flat path",
                    len(pubkeys),
                )
            finally:
                with self._lock:
                    self._building.discard(set_key)
            self.verifier.recorder.record(
                event,
                set_key=set_key.hex()[:16],
                validators=len(pubkeys),
                ms=round((_time.perf_counter() - t0) * 1000, 3),
                ok=error is None,
                error=error,
                shards=self.verifier.shards,
            )

        # non-daemon for the same reason as the warmup threads above
        _threading.Thread(target=_build, daemon=False, name=event).start()

    @staticmethod
    def _rows(pubkeys) -> Sequence[bytes]:
        """Accept either materialized rows or a lazy thunk — the steady
        state (cache hit) never needs the rows, so hot callers pass a
        callable and skip building a V-sized list per commit."""
        return pubkeys() if callable(pubkeys) else pubkeys

    def has_table(self, set_key: bytes) -> bool:
        with self._lock:
            return set_key in self._tables

    def rebuild(self, set_key: bytes, pubkeys: Sequence[bytes]) -> bool:
        """Proactively (re)build the device table for a validator set —
        the node's EVENT_VALIDATOR_SET_UPDATES subscriber calls this the
        moment an update lands so the table for the INCOMING set is warm
        before its first commit arrives, instead of that commit paying
        the decline-while-building miss.  Also re-probes the warmup
        bucket, which is shaped by the commit batch size.

        Returns True when a background build was kicked off; False when
        the set's table is already cached or building."""
        pk_copy = [bytes(pk) for pk in self._rows(pubkeys)]
        n = len(pk_copy)
        with self._lock:
            if set_key in self._tables or set_key in self._building:
                # table already live/underway; the bucket may still be stale
                self.verifier.rewarm(n)
                return False
            self._building.add(set_key)
        self.verifier.rewarm(n)
        self.verifier.metrics.table_rebuilds.inc()
        # warm at the whole-commit shape (one row per validator — what
        # verify_commit sends at steady state)
        self._build_in_background("verify.table_rebuild", set_key, pk_copy, n_warm=n)
        return True

    def install(self) -> "TableCache":
        batch_hook.set_indexed_verifier(self.verify_indexed)
        return self


# ---------------------------------------------------------------------------
# async batcher — trickling votes coalesce into TPU batches
# ---------------------------------------------------------------------------


class AsyncBatchVerifier(Service):
    """Deadline-flushed batcher (SURVEY.md §7 inversion #1).

    Callers enqueue single (pubkey, msg, sig) checks and await a future;
    a flusher coalesces the queue into one BatchVerifier call.  Consensus
    vote-add latency stays ~the coalescing window while throughput scales
    with batch size — the latency/batching tension called out in SURVEY.md
    §7.

    The window is ADAPTIVE to arrival rate (the fixed 2 ms quantum was a
    measured drag on small nets: a 4-validator round has ~2 vote hops per
    block and each paid the full quantum for a batch of one).  The flusher
    waits in "quiet windows": when recent inter-arrival gaps say more votes
    are imminent (storm or 100-val trickle) it keeps coalescing up to
    `flush_interval`; when the queue goes quiet it flushes after
    `flush_min` — sparse traffic pays ~flush_min, not the full quantum.
    `adaptive=False` restores the fixed-interval behavior.
    """

    def __init__(
        self,
        verifier: Optional[BatchVerifier] = None,
        max_batch: int = 4096,
        flush_interval: float = 0.002,
        max_pending: int = 65536,
        flush_min: float = 0.0002,
        adaptive: bool = True,
    ):
        super().__init__("batch-verifier")
        self.verifier = verifier or BatchVerifier()
        self.max_batch = max_batch
        self.flush_interval = flush_interval
        self.flush_min = min(flush_min, flush_interval)
        self.adaptive = adaptive
        self.max_pending = max_pending
        # (pubkey, msg, sig, fut, t_enqueued) — the timestamp feeds the
        # queue-wait histogram and the flight recorder's flush spans
        self._pending: List[Tuple[bytes, bytes, bytes, asyncio.Future, float]] = []
        self._wake: Optional[asyncio.Event] = None
        self._task: Optional[asyncio.Task] = None
        self._executor = None
        # EWMA of enqueue inter-arrival gap (seconds); None until 2 arrivals
        self._ewma_gap: Optional[float] = None
        self._last_arrival: Optional[float] = None
        self._enqueued = 0  # monotonic count, detects arrivals per window

    async def on_start(self) -> None:
        from concurrent.futures import ThreadPoolExecutor

        self._wake = asyncio.Event()
        # Jitted calls (and a cold-cache XLA compile, which is tens of
        # seconds) must never run on the event loop: with several reactors
        # sharing one loop an inline flush starves ping/pong, gossip and
        # consensus timeouts — the round-4 liveness bug.  One worker keeps
        # device dispatch serialized (the device is serial anyway).
        self._executor = ThreadPoolExecutor(max_workers=1, thread_name_prefix="bv-flush")
        self.verifier.start_warmup()  # compiles on its own thread; host path until warm
        # via spawn, not bare create_task: the scheduler profiler's
        # accounting trampoline rides the spawn path, and the flusher is
        # exactly the "verify" loop occupancy the attribution table needs
        self._task = self.spawn(self._flush_loop(), "flush-loop")

    async def on_stop(self) -> None:
        if self._task:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
        for _, _, _, fut, _ in self._pending:
            if not fut.done():
                fut.cancel()
        self._pending.clear()
        if self._executor is not None:
            self._executor.shutdown(wait=False)

    def _note_arrival(self, now: float, accepted: int) -> None:
        """Shared enqueue bookkeeping: one arrival-rate sample (a batch of
        N simultaneous entries must not convince the EWMA that votes
        arrive at nanosecond gaps), the arrivals counter the adaptive
        flusher watches, and the wake."""
        if self._last_arrival is not None:
            # one-sided clamp keeps a single long idle period (heights with
            # no votes) from poisoning the estimate for the next burst
            gap = min(now - self._last_arrival, self.flush_interval)
            self._ewma_gap = (
                gap if self._ewma_gap is None else 0.8 * self._ewma_gap + 0.2 * gap
            )
        self._last_arrival = now
        self._enqueued += accepted
        if self._wake and (self.adaptive or len(self._pending) >= self.max_batch):
            self._wake.set()

    def verify_one(self, pubkey: bytes, msg: bytes, sig: bytes) -> "asyncio.Future[bool]":
        loop = asyncio.get_event_loop()
        fut: asyncio.Future = loop.create_future()
        if len(self._pending) >= self.max_pending:
            # Backpressure: beyond the cap, verify inline on the host path.
            # Slower per-sig, but bounded memory and no dropped-vote false
            # negatives (a False here would penalize an honest peer).
            ok = batch_hook.host_batch_verify([pubkey], [msg], [sig])[0]
            fut.set_result(bool(ok))
            return fut
        now = loop.time()
        self._pending.append((pubkey, msg, sig, fut, now))
        self.verifier.recorder.record("verify.enqueue", pending=len(self._pending))
        self._note_arrival(now, accepted=1)
        return fut

    async def verify_direct(
        self, items: Sequence[Tuple[bytes, bytes, bytes]]
    ) -> List[bool]:
        """One PRE-BATCHED engine call on the flush executor, bypassing the
        coalescing flusher.  A relay `vote_batch` already has the engine's
        batch shape — routing it through verify_many buys nothing but two
        extra scheduling hops (enqueue→flusher-wake→quantum-sleep→flush),
        and on a congested loop (committee-scale in-proc nets run ~15k
        tasks) each hop is a full ready-queue drain: measured seconds of
        added latency per gossip hop at N=100.  The single flush-executor
        worker keeps device dispatch serialized with regular flushes."""
        if not items:
            return []
        pubkeys = [it[0] for it in items]
        msgs = [it[1] for it in items]
        sigs = [it[2] for it in items]
        loop = asyncio.get_event_loop()
        return await loop.run_in_executor(
            self._executor, self.verifier.verify, pubkeys, msgs, sigs
        )

    async def verify_bls_aggregates(
        self, items: Sequence[Tuple[Sequence[bytes], bytes, bytes]]
    ) -> List[bool]:
        """BLS aggregate-commit lane: each item is a FastAggregateVerify
        claim (pubkeys, msg, aggregate_sig).  The whole batch runs as ONE
        blinded pairing product (crypto/bls/scheme.batch_verify_aggregates)
        on the flush executor — serialized with device work, never on the
        event loop (a pure-python pairing is ~100 ms).  Results are
        memoized scheme-side, so the synchronous verify_commit path that
        follows a pre-verify lane (statesync/lite2/fastsync) hits the memo
        instead of re-pairing."""
        if not items:
            return []
        from .bls import scheme as _bls_scheme

        loop = asyncio.get_event_loop()
        t0 = loop.time()
        self.verifier.recorder.record(
            "verify.bls_agg", n=len(items), tier=_bls_scheme.active_tier()
        )
        if self._executor is not None:
            res = await loop.run_in_executor(
                self._executor, _bls_scheme.batch_verify_aggregates, list(items)
            )
        else:
            res = _bls_scheme.batch_verify_aggregates(list(items))
        m = self.verifier.metrics
        m.bls_agg_seconds.observe(loop.time() - t0)
        for _ in items:
            m.bls_agg_checks.inc()
        return res

    def verify_many(
        self, items: Sequence[Tuple[bytes, bytes, bytes]]
    ) -> List["asyncio.Future[bool]"]:
        """Enqueue a whole batch of (pubkey, msg, sig) checks as ONE
        arrival: everything is appended before the flusher is woken, so a
        decoded `vote_batch` reaches the device as one flush / one
        host-prep pass instead of defeating the engine vote-by-vote.
        Returns one future per item, in order."""
        loop = asyncio.get_event_loop()
        futs: List[asyncio.Future] = []
        overflow: List[Tuple[bytes, bytes, bytes, asyncio.Future]] = []
        now = loop.time()
        accepted = 0
        for pubkey, msg, sig in items:
            fut: asyncio.Future = loop.create_future()
            futs.append(fut)
            if len(self._pending) >= self.max_pending:
                overflow.append((pubkey, msg, sig, fut))
                continue
            self._pending.append((pubkey, msg, sig, fut, now))
            accepted += 1
        if items:
            self.verifier.recorder.record(
                "verify.enqueue_batch", n=len(items), pending=len(self._pending)
            )
            self._note_arrival(now, accepted)
        if overflow:
            # same backpressure contract as verify_one (beyond the cap,
            # host path; never drop) — but a whole batch of overflow run
            # inline would stall the event loop for the very backlog that
            # triggered it, so route it through the flush executor when
            # the service is running
            pks = [o[0] for o in overflow]
            over_msgs = [o[1] for o in overflow]
            over_sigs = [o[2] for o in overflow]
            if self._executor is not None:
                ex_fut = loop.run_in_executor(
                    self._executor, batch_hook.host_batch_verify, pks, over_msgs, over_sigs
                )

                def _deliver(done_fut, overflow=overflow):
                    try:
                        results = done_fut.result()
                    except Exception as e:
                        for _, _, _, fut in overflow:
                            if not fut.done():
                                fut.set_exception(
                                    RuntimeError(f"overflow verify failed: {e!r}")
                                )
                        return
                    for (_, _, _, fut), ok in zip(overflow, results):
                        if not fut.done():
                            fut.set_result(bool(ok))

                ex_fut.add_done_callback(_deliver)
            else:
                results = batch_hook.host_batch_verify(pks, over_msgs, over_sigs)
                for (_, _, _, fut), ok in zip(overflow, results):
                    fut.set_result(bool(ok))
        return futs

    def _quiet_window(self) -> float:
        """How long the flusher waits for MORE arrivals before flushing.
        Large when recent gaps say votes are streaming in (coalesce them),
        floor when the expected next arrival is beyond the deadline anyway
        (waiting buys nothing but latency)."""
        gap = self._ewma_gap
        if gap is None or 4 * gap >= self.flush_interval:
            return self.flush_min
        return max(4 * gap, self.flush_min)

    async def _wait_for_batch(self) -> None:
        """Adaptive coalescing: sleep until there is work, then extend in
        quiet windows while arrivals continue, capped at flush_interval."""
        loop = asyncio.get_event_loop()
        if not self._pending:
            await self._wake.wait()
            self._wake.clear()
        deadline = loop.time() + self.flush_interval
        while self._pending and len(self._pending) < self.max_batch:
            remaining = deadline - loop.time()
            if remaining <= 0:
                break
            before = self._enqueued
            try:
                await asyncio.wait_for(
                    self._wake.wait(), timeout=min(self._quiet_window(), remaining)
                )
            except asyncio.TimeoutError:
                if self._enqueued == before:
                    break  # a full quiet window with no arrivals: flush now
            self._wake.clear()

    async def _flush_loop(self) -> None:
        loop = asyncio.get_event_loop()
        while True:
            if self.adaptive:
                await self._wait_for_batch()
            else:
                try:
                    await asyncio.wait_for(self._wake.wait(), timeout=self.flush_interval)
                except asyncio.TimeoutError:
                    pass
                self._wake.clear()
            if not self._pending:
                continue
            # chunk at max_batch so one storm doesn't produce an unbounded
            # device shape; the remainder flushes on the next iteration
            batch = self._pending[: self.max_batch]
            del self._pending[: self.max_batch]
            if len(self._pending) >= self.max_batch and self._wake:
                self._wake.set()
            now = loop.time()
            wait_s = max(0.0, now - batch[0][4])  # oldest entry's queue wait
            quantum_s = self._quiet_window() if self.adaptive else self.flush_interval
            m = self.verifier.metrics
            m.queue_wait_seconds.observe(wait_s)
            m.flush_quantum_seconds.set(quantum_s)
            self.verifier.recorder.record(
                "verify.flush",
                batch=len(batch),
                wait_ms=round(wait_s * 1000, 3),
                quantum_ms=round(quantum_s * 1000, 3),
                shards=self.verifier.shards,
            )
            pubkeys = [b[0] for b in batch]
            msgs = [b[1] for b in batch]
            sigs = [b[2] for b in batch]
            try:
                results = await loop.run_in_executor(
                    self._executor, self.verifier.verify, pubkeys, msgs, sigs
                )
            except asyncio.CancelledError:
                for _, _, _, fut, _ in batch:
                    if not fut.done():
                        fut.cancel()
                raise
            except Exception as e:
                # a dead flusher would strand every pending + future caller;
                # fail this batch's futures and keep the loop alive
                for _, _, _, fut, _ in batch:
                    if not fut.done():
                        fut.set_exception(RuntimeError(f"batch verify failed: {e!r}"))
                continue
            for (_, _, _, fut, _), ok in zip(batch, results):
                if not fut.done():
                    fut.set_result(bool(ok))
