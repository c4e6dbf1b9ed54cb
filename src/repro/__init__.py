"""repro — BigDatalog-X: recursive Datalog analytics + multi-pod LM framework in JAX.

The paper's primary contribution (Datalog with aggregates-in-recursion under
PreM, parallel semi-naive evaluation) lives in ``repro.core``.  The shared
distribution substrate (mesh, sharding rules, launcher, roofline) also serves
the ten assigned LM architectures in ``repro.models`` / ``repro.configs``.

x64 is enabled package-wide: the relational engine packs tuples into int64
keys (see ``repro.core.relation``).  All model code uses explicit dtypes, so
the LM stack is unaffected by the wider defaults.  The frontier fixpoints
keep their (B, n) tables 32-bit (the chip emulates 64-bit arithmetic); only
scalar counters are 64-bit.
"""
import os

import jax

jax.config.update("jax_enable_x64", True)

__version__ = "1.0.0"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX reads it and no
    other directory is set.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``: the directory is part of what a later run
    needs to find an entry again, so it is never a temporary or per-process
    one.  Entry points call this; importing the package does not.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
