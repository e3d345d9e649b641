"""Opt-in x64 mode: the library-wide gate for 64-bit keys and payloads.

The library runs jax in its default 32-bit mode and rejects 64-bit
dtypes at the planner door (``planner.check_key_dtype``) — the safe
default, because without ``jax_enable_x64`` the device sort would
silently truncate int64 keys to 32 bits. This module is the single
switch that lifts that contract end to end: when x64 mode is on, the
door check admits int64/uint64/float64 keys and values, the multi-key
pack budget widens from 31 to 63 bits (``keyenc.pack_budget_bits``) so
timestamp/id tuples fuse into ONE int64 sort, and every backend's
sentinel/staging machinery — already dtype-driven
(``kernels.ops.sentinel_for``) — picks the width-correct int64/float64
sentinel automatically.

Three equivalent ways to opt in, mirroring the ``jax_enable_x64``
config pattern:

  * environment — ``REPRO_X64=1`` before the first sort (read lazily,
    so it works under pytest/CI env injection);
  * process-wide — ``repro.enable_x64()`` (also flips jax's own
    ``jax_enable_x64`` flag, which is required for 64-bit device
    arrays; visible to background serve threads);
  * per-request — ``SortLimits(x64=True)`` admits wide dtypes for that
    request only (and ensures the jax flag); ``SortLimits(x64=False)``
    pins a request to the 32-bit contract even when the ambient mode
    is on.

``x64_mode()`` is the scoped variant for tests and benchmarks: it sets
the library flag and enters ``jax.enable_x64(True)`` so the
*thread-local* jax trace context widens, then restores both on exit —
nothing leaks into subsequent 32-bit work on the same thread. (The jax
x64 flag is part of the jit trace key, so toggling retraces programs
instead of reusing stale 32-bit ones.) Note the thread-local scope: a
``SortServer``'s flush loop runs on its own thread and only sees the
process-wide ``enable_x64()`` switch.

The default 32-bit path is bit-identical with the mode off OR on for
32-bit inputs whose packs fit 31 bits — width is a threaded parameter,
not an ambient assumption (see ``keyenc.PackSpec.pack_dtype``).
"""
from __future__ import annotations

import contextlib
import os

# None = not yet resolved (fall back to the REPRO_X64 env var on first
# read); True/False = set explicitly via enable_x64() / x64_mode()
_STATE: dict = {"enabled": None}


def _env_enabled() -> bool:
    return os.environ.get("REPRO_X64", "").strip().lower() in (
        "1", "true", "on", "yes"
    )


def ensure_jax_x64() -> None:
    """Flip jax's own ``jax_enable_x64`` flag on (idempotent).

    Without it, 64-bit numpy inputs are truncated at ``jnp.asarray``
    time — the exact hazard the 32-bit door check exists to prevent."""
    import jax

    if not jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", True)


def x64_enabled() -> bool:
    """Is x64 mode on (explicit switch, scoped block, or REPRO_X64)?"""
    st = _STATE["enabled"]
    if st is None:
        if not _env_enabled():
            return False
        # env opt-in: resolve once and make the device side wide too
        _STATE["enabled"] = True
        ensure_jax_x64()
        return True
    return bool(st)


def enable_x64(on: bool = True) -> None:
    """Process-wide x64 switch (``repro.enable_x64()``).

    ``on=True`` admits 64-bit keys/values at the planner door and flips
    jax's ``jax_enable_x64`` so device arrays really are 64-bit — the
    switch serve flush threads see. ``on=False`` restores the 32-bit
    contract (and the jax flag); arrays created while the mode was on
    keep their dtype, they are simply rejected at the door again."""
    import jax

    _STATE["enabled"] = bool(on)
    if on:
        ensure_jax_x64()
    elif jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", False)


@contextlib.contextmanager
def x64_mode(on: bool = True):
    """Scoped x64 mode for tests/benchmarks: restores everything on exit.

    Sets the library flag and enters ``jax.enable_x64(True)``
    (thread-local jax trace context), so code after the block — on this
    thread — is back on the 32-bit contract with no global state left
    behind."""
    import jax

    prev = _STATE["enabled"]
    _STATE["enabled"] = bool(on)
    try:
        if on:
            with jax.enable_x64(True):
                yield
        else:
            yield
    finally:
        _STATE["enabled"] = prev
