"""SEINE reproduction: segment-based indexing for neural IR, grown into a
distributed jax system (offline index build / online retrieval split, §2)."""


def use_compile_cache() -> None:
    """Keep JAX's persistent compile cache where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads the variable itself), else at ``.jax_cache/`` in the
    checkout: a fixed path, since the path is part of the cache key."""
    import os

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))
