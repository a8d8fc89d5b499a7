"""Where JAX's persistent compilation cache lives.

Every process entry point on the chip path (``chip_smoke.py``, ``bench.py``,
``serving.fleet.replica_main``, ``scripts/chip_microbench.py``) calls
``place()`` before its first compile, so the processes of one chip session
share compiled programs instead of each paying XLA again.

The directory is part of the cache key, so it must not move: it is either
the one the environment names or a fixed path derived from this package's
own location — never a temporary name, a pid or a time.
"""

import os

__all__ = ["place", "DEFAULT_DIR"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def place():
    """Returns the cache directory in effect (None: no cache).

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no directory is
    set in code.  Unset, on an accelerator: ``<checkout>/.jax_cache``, with
    the compile-time floor off so that what a run caches does not depend on
    how long a compile happened to take (a second run must add nothing).
    On an accelerator, either way, the cache is keyed on the programs'
    metadata too (see below)."""
    import jax

    from .monitor.recompile import compile_ledger

    # every chip entry point comes through here before its first compile:
    # from now on the process accounts for what it builds or loads
    compile_ledger()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if jax.devices()[0].platform == "cpu":
        # CPU compiles are cheap, and under jaxlib 0.9.0 an XLA:CPU
        # executable that was LOADED from this cache re-serializes without
        # its kernels: warm.py would publish it, and the replica that
        # deserializes it dies at first dispatch ("Function
        # concatenate.1_kernel not found")
        return env or None
    # JAX hashes a program AFTER stripping its debug info, which is where
    # jax.named_scope names and source lines live: a step that another
    # commit compiled is then served under the same key with THAT commit's
    # names, and monitor.devscope (or any profile) reads stale scopes or
    # none.  Keyed on the metadata, an edit on a program's traced path makes
    # its next run compile anew; a second run of the same tree still adds
    # no entry.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_DIR
