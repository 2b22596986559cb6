"""Serving layer: continuous-batching engine (mechanism), schedulers
(policy), prefix/cross caches, dp router, and samplers.  See
ARCHITECTURE.md for the end-to-end map and per-module invariants."""
from repro.serving.engine import (EngineStats, PageTransferError,  # noqa: F401
                                  ReplicaStats, Request, ServingEngine)
from repro.serving.policies import FairScheduler, PriorityScheduler  # noqa: F401
from repro.serving.prefix_cache import (CrossKVCache, HostSpillStore,  # noqa: F401
                                        RadixPrefixCache)
from repro.serving.router import Router  # noqa: F401
from repro.serving.sampler import SamplerConfig, sample_from_logits  # noqa: F401
from repro.serving.scheduler import Admission, FCFSScheduler, Scheduler  # noqa: F401
