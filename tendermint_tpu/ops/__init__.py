"""TPU compute kernels (JAX/XLA; Pallas where profiling demands).

All field arithmetic is native int32 (13-bit limbs) — TPUs have no native
int64, so the round-1 int64 design paid several emulated ops per multiply.

The persistent compile cache is placed HERE and nowhere else (the curve
kernels take tens of seconds to compile; the cache lets reruns and sibling
processes skip that).  Every module that touches jax imports this package
first.  Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and
nothing is overridden; otherwise the cache is `.jax_cache` in the checkout
that holds this package.  The directory is part of the cache key, so it
must be the same path on every run.
"""

import os

import jax

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
            ".jax_cache",
        ),
    )
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
# A Pallas kernel's body goes into the cache key with its source locations,
# and a location holds the caller's frames as well: the ladder is traced once
# a process, by whichever of the warm-up and table-build threads gets there
# first, so with full tracebacks the same program had two keys (a warm cache
# missed on the chip; PERF.md, PR 28).  One frame, the kernel's own line, is
# the same from every caller.
jax.config.update("jax_include_full_tracebacks_in_locations", False)

from . import fe  # noqa: E402
from . import ed25519 as ed25519_kernel  # noqa: E402

__all__ = ["fe", "ed25519_kernel"]
