"""Where XLA's persistent compilation cache lives: one place decides.

``JAX_COMPILATION_CACHE_DIR`` set by the operator wins and nothing is
set in code — jax reads that variable itself.  Unset, the cache goes to
ONE fixed directory inside the checkout: the directory is part of the
cache key, so a temp name, a pid or a timestamp would never hit.  The
choice is exported through the same variable, so every process this
one spawns (actors, batchers, eval matches, bench children — all fresh
interpreters) lands on the same directory without being told.

What it buys on a cold machine: the cost harvest
(telemetry/costmodel.py) compiles each guarded program once ahead of
its first call; with the cache on, the call's own compile reads that
entry back instead of compiling the same program a second time.
"""

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
