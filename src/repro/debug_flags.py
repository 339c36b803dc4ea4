"""The single parse point for every ``REPRO_*`` environment flag.

Before this module existed, each consumer re-parsed the environment
independently (``serve/engine.py`` captured ``REPRO_DEBUG`` once at
construction, ``kernels/ops.py`` and ``models/linear.py`` read
``REPRO_DEQUANT_IMPL`` per call), so a mid-process change — a test
monkeypatching the environment, a driver flipping debug on for one phase
— was observed by some modules and not others. Now every read funnels through :func:`flags`, which re-reads the
environment through one code path and hands back one immutable typed
snapshot: either every module sees a change, or none does, and there is
exactly one place where the string -> typed-value parse can be wrong.

The repro-lint rule RL008 (``repro.analysis``) enforces the funnel
statically: any ``os.environ`` access naming a ``REPRO_*`` flag outside
this module is a lint error.

Flags:
  REPRO_DEBUG=1          per-step engine/pool invariant validation
  REPRO_SANITIZE=1       compile-count sanitizer: engine jit entry points
                         record one tracing event per compiled variant
                         (see repro.analysis.sanitize)
  REPRO_DEQUANT_IMPL     "pallas" forces the Pallas lowering (interpret
                         mode on CPU), "ref" forces the jnp reference,
                         "" picks by backend
  REPRO_AUTOTUNE         kernel tile selection (kernels/autotune.py):
                         "" (default) uses the warm JSON cache when one is
                         readable, else the deterministic fallback table;
                         "0" always uses the table (CI / replay); "1"
                         measures real pallas_call candidates and records
                         the winners
  REPRO_AUTOTUNE_CACHE   path of the autotune JSON config cache ("" = no
                         on-disk cache: measured winners stay in-process)

No flag turns a kernel failure into a fallback: a Pallas kernel that does
not lower raises, on the chip and in interpret mode alike. The only
fused -> gather downgrade left is the engine's answer to the injected
``kernel_fault`` (serve/faults.py).
"""
from __future__ import annotations

import dataclasses
import os


@dataclasses.dataclass(frozen=True)
class Flags:
    """Typed snapshot of the REPRO_* environment flags."""

    debug: bool
    sanitize: bool
    dequant_impl: str  # "", "pallas", or "ref"
    autotune: str  # "", "0", or "1"
    autotune_cache: str  # cache file path ("" = none)


_ENV_KEYS = ("REPRO_DEBUG", "REPRO_SANITIZE", "REPRO_DEQUANT_IMPL",
             "REPRO_AUTOTUNE", "REPRO_AUTOTUNE_CACHE")
_VALID_IMPLS = ("", "pallas", "ref")
_VALID_AUTOTUNE = ("", "0", "1")

# (raw env tuple, parsed Flags) — rebuilt only when the raw values change,
# so hot callers pay four dict lookups, not a dataclass construction
_cache: tuple = (None, None)


def flags() -> Flags:
    """Current flag snapshot. Re-reads the environment on every call (one
    parse point, consistently observed by every module), memoized on the
    raw values so unchanged environments return the same object."""
    global _cache
    raw = tuple(os.environ.get(k, "") for k in _ENV_KEYS)
    if raw != _cache[0]:
        impl = raw[2]
        if impl not in _VALID_IMPLS:
            raise ValueError(
                f"REPRO_DEQUANT_IMPL={impl!r}: expected one of "
                f"{_VALID_IMPLS} (typo'd values used to silently fall "
                f"through to the backend default)")
        tune = raw[3]
        if tune not in _VALID_AUTOTUNE:
            raise ValueError(
                f"REPRO_AUTOTUNE={tune!r}: expected one of "
                f"{_VALID_AUTOTUNE}")
        _cache = (raw, Flags(debug=raw[0] == "1",
                             sanitize=raw[1] == "1",
                             dequant_impl=impl,
                             autotune=tune,
                             autotune_cache=raw[4]))
    return _cache[1]


def debug_enabled() -> bool:
    return flags().debug


def sanitize_enabled() -> bool:
    return flags().sanitize


def dequant_impl() -> str:
    return flags().dequant_impl


def autotune_mode() -> str:
    return flags().autotune


def autotune_cache_path() -> str:
    return flags().autotune_cache
