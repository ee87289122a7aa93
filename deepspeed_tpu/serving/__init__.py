"""deepspeed_tpu.serving — prefix-cached, SLO-aware serving frontend.

The layer the reference ships as DeepSpeed-MII on top of FastGen
(mii/batching/ragged_batching.py): request lifecycle + admission control,
a radix prefix cache over ref-counted KV pages, a SplitFuse token-budget
scheduling policy, and per-token streaming with TTFT/TPOT observability.
Here it drives :class:`~deepspeed_tpu.inference.engine_v2.
RaggedInferenceEngineTPU` through its step's two halves, ``launch`` and
``collect`` (``step_with_budget`` is the two back to back) —
the engine stays a pure batch machine; everything traffic-shaped lives in
this package. See docs/serving.md.
"""

from deepspeed_tpu.serving.autoscaler import Autoscaler  # noqa: F401
from deepspeed_tpu.serving.frontend import ServingFrontend, adopt_cached  # noqa: F401
from deepspeed_tpu.serving.handoff import (PageBundle, adopt_bundle,  # noqa: F401
                                           export_bundle, verify_bundle)
from deepspeed_tpu.serving.kvtier import KVTier, TornSpill  # noqa: F401
from deepspeed_tpu.serving.metrics import Histogram, ServingMetrics  # noqa: F401
from deepspeed_tpu.serving.prefix_cache import PrefixCache, PrefixMatch  # noqa: F401
from deepspeed_tpu.serving.queue import AdmissionError, AdmissionQueue  # noqa: F401
from deepspeed_tpu.serving.request import Request, RequestState  # noqa: F401
from deepspeed_tpu.serving.router import (CircuitBreaker, LocalReplica,  # noqa: F401
                                          Router, RouterRequest)
from deepspeed_tpu.serving.scheduler import TokenBudgetPolicy  # noqa: F401

__all__ = ["ServingFrontend", "adopt_cached", "Request", "RequestState",
           "AdmissionQueue", "AdmissionError", "PrefixCache", "PrefixMatch",
           "TokenBudgetPolicy", "ServingMetrics", "Histogram",
           "Router", "RouterRequest", "LocalReplica", "CircuitBreaker",
           "PageBundle", "export_bundle", "adopt_bundle", "verify_bundle",
           "KVTier", "TornSpill", "Autoscaler"]
