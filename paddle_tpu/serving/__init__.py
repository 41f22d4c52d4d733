"""Continuous-batching serving engine (slot-pool in-graph decode over a
block-paged KV arena).

``ContinuousBatchingEngine`` keeps S sequence slots alive inside ONE
jitted decode program; ``Scheduler`` admits ragged requests into free
slots; ``Server`` is the loop + metrics. Greedy streams are
bit-identical to per-request ``generate()`` calls while sustaining
strictly higher aggregate tokens/s on mixed-length traffic. The AOT
path (``inference.export_decoder(engine_slots=...)`` +
``GenerationPredictor.serve``) serves the same engine from the
serialized artifact alone."""
from .autoscaler import (Autoscaler, AutoscalerConfig, DecisionKernel,
                         Observation)
from .durability import PrefixSpillStore, WriteAheadJournal
from .engine import ContinuousBatchingEngine, slot_sample_logits
from .fleet import (DecodeWorker, Fleet, FleetRouter, InProcessTransport,
                    PrefillPagedEngine, PrefillWorker, SocketTransport,
                    Transport, TransportError)
from .frontend import FairScheduler, Frontend, TenantConfig, TokenStream
from .handoff import (KVHandoff, decode_handoff, encode_handoff,
                      reshard_kv_chunks)
from .loadgen import Trace, TraceConfig, generate_trace, replay
from .hybrid import HybridPagedEngine, HybridPagedStepBackend
from .paging import (BlockManager, PagedArtifactStepBackend,
                     PagedModelStepBackend)
from .prefix_cache import (PrefixCacheDirectory, adopt_prefix,
                           extract_prefix)
from .quant import QuantConfig
from .resilience import RequestFailure, ResilienceConfig
from .scheduler import Request, ResumeState, Scheduler
from .server import Server
from .spec import (SpecConfig, SpecPagedEngine, SpecPagedStepBackend,
                   ngram_propose)
from .tp import ShardedPagedStepBackend, TPConfig

__all__ = ["Autoscaler", "AutoscalerConfig", "ContinuousBatchingEngine",
           "DecisionKernel", "Observation", "BlockManager", "DecodeWorker",
           "FairScheduler", "Fleet", "FleetRouter", "Frontend",
           "HybridPagedEngine", "HybridPagedStepBackend",
           "InProcessTransport", "KVHandoff",
           "PagedArtifactStepBackend", "PagedModelStepBackend",
           "PrefillPagedEngine", "PrefillWorker",
           "PrefixCacheDirectory", "PrefixSpillStore", "QuantConfig",
           "Request", "RequestFailure", "ResilienceConfig",
           "ResumeState", "Scheduler", "Server", "SocketTransport",
           "SpecConfig", "SpecPagedEngine", "SpecPagedStepBackend",
           "ShardedPagedStepBackend",
           "TPConfig", "TenantConfig", "TokenStream", "Transport",
           "Trace", "TraceConfig", "TransportError",
           "WriteAheadJournal", "adopt_prefix",
           "decode_handoff", "encode_handoff", "extract_prefix",
           "generate_trace", "ngram_propose", "replay",
           "reshard_kv_chunks", "slot_sample_logits"]
