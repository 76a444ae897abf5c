"""mx.serving — production inference serving engine (docs/SERVING.md).

The millions-of-users half of the north star: the training substrate
(AOT lowering + the persistent compile cache, the dispatch window, the
telemetry catalog, the program-lint gates) turned into a serving path.

- :class:`CompiledPredictor` — AOT-compiled inference executables per
  leading-dim shape bucket: taping suspended, params resident on
  device, warm-started from the persistent compile cache, with the
  same static-analysis gates (``analyze()``/``memory_report()``/
  fusion census) as the training step.
- :class:`DynamicBatcher` — bounded-queue request coalescing into the
  bucketed shapes the compile cache keys on (pad-to-bucket with a
  valid-row mask; ``MXNET_SERVING_MAX_BATCH`` /
  ``MXNET_SERVING_BATCH_TIMEOUT_MS``), dispatched pipelined through a
  :class:`~mxnet_tpu.engine.DispatchWindow` so the device never idles
  between micro-batches — now with per-request deadlines
  (``submit(deadline_ms=)``), admission control/load shedding
  (``MXNET_SERVING_SHED``), graceful drain, and typed failures
  (an accepted request never hangs).
- :mod:`.resilience` — :class:`ServingSupervisor` (device-loss
  recovery riding the elastic seams: classify via
  ``elastic.detect.classify``, rebuild over ``available_devices()``
  with cache-warm AOT buckets, re-enqueue in-flight requests exactly
  once), :class:`CircuitBreaker`, and the typed error taxonomy
  (:class:`DeadlineExceeded` / :class:`Overloaded` /
  :class:`ServingShutdown`).
- :mod:`.fleet` — :class:`FleetController`/:class:`FleetRouter`: a
  multi-replica serving fleet (one predictor+batcher+supervisor per
  device, AOT-warm from the shared compile cache) with least-wait
  routing, replica-loss failover onto the survivors (exactly-once
  re-enqueue), drain-then-retire on scoped preemption notices,
  autoscaling, and zero-downtime rolling weight swaps
  (``mx_fleet_*`` telemetry; docs/SERVING.md "Serving fleet").
- :func:`predictor_for` — bf16/fp16/int8 serving variants through the
  existing AMP and post-training-quantization paths.
- :mod:`.loadgen` — closed-/open-loop load generation with per-request
  outcome census {ok, rejected, deadline_missed, error}, goodput vs
  raw QPS, and exact p50/p99.

Observability: ``mx_serving_*`` series in the telemetry catalog —
queue depth, in-flight micro-batches, batch occupancy, request-latency
histogram, rejected/deadline-missed/retries/recoveries counters,
breaker state, drain duration (docs/OBSERVABILITY.md).
"""
from __future__ import annotations

from .resilience import (CircuitBreaker, DeadlineExceeded, Overloaded,
                         ServingShutdown, ServingSupervisor,
                         default_deadline_ms, queue_timeout_s, shed_mode,
                         transient_retries)
from .predictor import CompiledPredictor, DEFAULT_BUCKETS, predictor_for
from .batcher import (DynamicBatcher, ServingFuture, batch_timeout_s,
                      max_batch_rows, queue_depth)
from .kvcache import (KV_PAGE_SIZE, PagedKVCache, pages_needed,
                      prefix_hash)
from .decode import (DecodeEngine, DecodeStream, ModelDrafter,
                     NgramDrafter, TinyDecoder, kv_page_size,
                     prefill_chunk, prefix_share, run_decode,
                     slot_ladder, spec_k)
from .fleet import (FleetController, FleetEvent, FleetRouter,
                    fleet_max_replicas, fleet_min_replicas,
                    fleet_replicas, fleet_restart_retries,
                    fleet_scale_down_wait_s, fleet_scale_up_wait_s)
from . import fleet
from . import loadgen
from . import resilience
from . import decode
from . import kvcache

__all__ = ["CompiledPredictor", "DynamicBatcher", "ServingFuture",
           "predictor_for", "DEFAULT_BUCKETS", "loadgen", "resilience",
           "max_batch_rows", "batch_timeout_s", "queue_depth",
           "CircuitBreaker", "ServingSupervisor", "DeadlineExceeded",
           "Overloaded", "ServingShutdown", "default_deadline_ms",
           "queue_timeout_s", "shed_mode", "transient_retries",
           "decode", "kvcache", "DecodeEngine", "DecodeStream",
           "TinyDecoder", "PagedKVCache", "KV_PAGE_SIZE",
           "pages_needed", "run_decode", "slot_ladder", "kv_page_size",
           "prefill_chunk", "prefix_hash", "NgramDrafter",
           "ModelDrafter", "spec_k", "prefix_share",
           "fleet", "FleetController", "FleetRouter",
           "FleetEvent", "fleet_replicas", "fleet_min_replicas",
           "fleet_max_replicas", "fleet_scale_up_wait_s",
           "fleet_scale_down_wait_s", "fleet_restart_retries"]
