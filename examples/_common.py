"""Shared example bootstrap."""


def setup_jax():
    """Import jax (``JAX_PLATFORMS`` selects the backend) and place the
    persistent compile cache before the first compile."""
    import jax

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    return jax
