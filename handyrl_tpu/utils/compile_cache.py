"""Where XLA's persistent compilation cache lives: one place decides.

``JAX_COMPILATION_CACHE_DIR`` set by the operator wins and nothing is
set in code — jax reads that variable itself.  Unset, the cache goes to
ONE fixed directory inside the checkout: the directory is part of the
cache key, so a temp name, a pid or a timestamp would never hit.  The
choice is exported through the same variable, so every process this
one spawns (actors, batchers, eval matches — all fresh
interpreters) lands on the same directory without being told.

What it buys on a cold machine: the cost harvest
(telemetry/costmodel.py) compiles each guarded program once ahead of
its first call; with the cache on, the call's own compile reads that
entry back instead of compiling the same program a second time.
"""

import contextlib
import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache():
    """Call before the first compile; returns the directory in use."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    os.environ[ENV_VAR] = _DEFAULT
    jax = sys.modules.get("jax")
    if jax is not None:   # it read the (then unset) variable at import
        jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    return _DEFAULT


def metadata_in_key():
    """A context, for the calling thread alone, in which a program's
    cache key also holds its metadata (scopes, source lines).

    JAX keys an entry without them, so a program whose metadata
    changed and whose operations did not loads the entry, and with it
    the metadata, of whichever build wrote it first.  The fused step's
    scopes are READ (telemetry/devtrace.py joins a device trace's ops
    to them), so the trainer thread compiles that one program in this
    context: an entry never answers for another build's scopes.  The
    price is a cold compile of the step for a checkout that moved a
    line under it.  The switch is JAX's own
    ``jax_compilation_cache_include_metadata_in_key``, taken in its
    thread-local form so that no other thread's compile is re-keyed; a
    JAX that lacks that form keys as it always did, and devtrace says
    so when a text names no scope."""
    try:
        from jax._src.config import (
            compilation_cache_include_metadata_in_key as state)

        return state(True)
    except Exception:
        return contextlib.nullcontext()
