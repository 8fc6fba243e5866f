"""Serving: jitted prefill/decode-loop engine + slot-based continuous
batching scheduler over a paged (block-pool) KV cache."""
from .attribution import (RequestAttribution, attribution_report,  # noqa: F401
                          explain)
from .chaos import ChaosInjector  # noqa: F401
from .engine import (ServeConfig, jit_decode_step,  # noqa: F401
                     jit_paged_decode_loop, jit_paged_join)
from .kvpool import KVPool, PageError  # noqa: F401
from .scheduler import Batcher, ContinuousBatcher  # noqa: F401
from .telemetry import MetricsRegistry, Tracer  # noqa: F401
