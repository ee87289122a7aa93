"""deepspeed_tpu — a TPU-native large-scale training & inference framework.

Provides the capabilities of DeepSpeed (reference: deepspeed/__init__.py —
``initialize``:78, ``init_inference``:302) re-designed for TPU: SPMD over a
``jax.sharding.Mesh``, ZeRO as sharding layouts, XLA collectives over
ICI/DCN, Pallas kernels for hot ops.
"""

import time as _time

_IMPORT_T0 = _time.perf_counter()     # ``setup/import`` starts here ...

from deepspeed_tpu.version import __version__  # noqa: E402
from deepspeed_tpu import comm  # noqa: E402,F401
from deepspeed_tpu.config import AUTO, DeepSpeedTPUConfig  # noqa: E402,F401
from deepspeed_tpu.parallel.mesh import build_mesh, get_mesh, mesh_from_config  # noqa: E402,F401

__all__ = ["__version__", "DeepSpeedTPUConfig", "AUTO", "build_mesh",
           "get_mesh", "mesh_from_config", "comm", "initialize",
           "init_inference", "add_config_arguments",
           "default_inference_config", "tp_model_init"]


def add_config_arguments(parser):
    """Add the framework's CLI arguments to an argparse parser
    (reference deepspeed/__init__.py:279 ``add_config_arguments`` /
    ``_add_core_arguments``:240 — same flag names so launch scripts
    port unchanged; the deprecated --deepscale aliases are accepted
    too)."""
    group = parser.add_argument_group("DeepSpeed",
                                      "deepspeed_tpu configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable the engine (helper flag for user "
                            "code, no impact on the backend)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="json configuration file for "
                            "deepspeed_tpu.initialize()")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help="Deprecated alias of --deepspeed")
    group.add_argument("--deepscale_config", default=None, type=str,
                       help="Deprecated alias of --deepspeed_config")
    return parser


def default_inference_config():
    """Default inference configuration dict (reference
    deepspeed/__init__.py:295)."""
    from deepspeed_tpu.inference.engine import DeepSpeedTPUInferenceConfig
    return DeepSpeedTPUInferenceConfig().model_dump()


def tp_model_init(model, tp_size, dtype, config=None, rng=None):
    """Initialize a model tensor-parallel (reference
    deepspeed/__init__.py:380 ``tp_model_init`` — there it wraps a
    torch module in the TpTrainingManager; here the model IS a config +
    params pytree, so this builds/validates a mesh with a ``tp_size``
    model axis and jit-initializes the params with TP ``out_shardings``
    so they never materialize unsharded).

    ``config`` is accepted for reference-signature parity only (the
    reference reads kernel-injection knobs from it that have no TPU
    analogue) and is ignored with a warning when set. Returns
    ``(params, mesh)``.
    """
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import transformer
    from deepspeed_tpu.parallel.mesh import get_mesh, has_mesh
    from deepspeed_tpu.utils.logging import logger
    from jax.sharding import NamedSharding

    if config is not None:
        logger.warning(
            "tp_model_init: config is a reference-parity argument and "
            "is ignored; pass the dict to deepspeed_tpu.initialize()")
    if has_mesh():
        mesh = get_mesh()
        if mesh.shape.get("model", 1) != tp_size:
            raise ValueError(
                f"tp_model_init(tp_size={tp_size}) conflicts with the "
                f"live mesh (model axis {mesh.shape.get('model', 1)}); "
                "build_mesh(model=tp_size, ...) with your full topology "
                "first — silently replacing the process mesh would "
                "invalidate it for everything else")
    else:
        mesh = build_mesh(model=tp_size)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    specs = transformer.partition_specs(model, zero_stage=0, tp=tp_size > 1)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda s: not isinstance(s, dict))
    jdt = {"bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
           "float32": jnp.float32, "fp32": jnp.float32,
           "float16": jnp.float16, "fp16": jnp.float16}.get(str(dtype),
                                                            dtype)
    init = jax.jit(
        lambda r: jax.tree.map(
            lambda a: a.astype(jdt)
            if jnp.issubdtype(a.dtype, jnp.floating) else a,
            transformer.init_params(model, r)),
        out_shardings=shardings)
    return init(rng), mesh


def initialize(*args, **kwargs):
    """Create a training engine (reference deepspeed/__init__.py:78).

    Deferred import so config/comm utilities stay importable without
    triggering engine deps.
    """
    from deepspeed_tpu.runtime.engine import initialize as _initialize
    return _initialize(*args, **kwargs)


def init_inference(*args, **kwargs):
    """Create an inference engine (reference deepspeed/__init__.py:302)."""
    from deepspeed_tpu.inference.engine import init_inference as _init_inference
    return _init_inference(*args, **kwargs)


#: ... and ends here: the package's own import on ``time.perf_counter``.
#: Telemetry may not load with the package, so the first
#: ``compile_monitor.install()`` publishes it (``setup/import_seconds``)
_IMPORT_SPAN = (_IMPORT_T0, _time.perf_counter())
