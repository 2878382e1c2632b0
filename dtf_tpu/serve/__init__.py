"""Serving subsystem: checkpoint→inference bridge, KV-cache decode,
dynamic batching engine (see docs in each module)."""

from dtf_tpu.serve.bridge import (load_for_serving,       # noqa: F401
                                  load_inference_variables,
                                  place_for_serving,
                                  serving_memory_plan, serving_mesh)
from dtf_tpu.serve.decode import (Decoder,                # noqa: F401
                                  make_decode_model,
                                  teacher_forced_logits)
from dtf_tpu.serve.engine import (Backpressure, PagePool,  # noqa: F401
                                  ServeEngine, ServeRequest, ServeResult)
from dtf_tpu.serve.metrics import ServingStats, collect_stats  # noqa: F401
from dtf_tpu.serve.replica import ReplicaServer  # noqa: F401
from dtf_tpu.serve.rollout import (RolloutController,  # noqa: F401
                                   RolloutState)
from dtf_tpu.serve.router import (DeadlineExceeded, Router,  # noqa: F401
                                  RouterResult, replica_spawner)
