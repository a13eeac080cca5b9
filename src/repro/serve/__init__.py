from repro.serve.serve import (
    ServeConfig,
    make_decode_step,
    make_prefill_step,
    make_prefill_chunk_step,
    make_serve_decode_step,
    make_spec_verify_step,
    BatchScheduler,
    RequestHandle,
)
from repro.serve.spec import draft_tokens
from repro.serve.traffic import (
    TrafficConfig,
    TrafficRequest,
    generate_workload,
    replay,
)
from repro.serve.faults import (
    FaultConfig,
    FaultEvent,
    FaultInjector,
    generate_faults,
)

__all__ = [
    "ServeConfig", "make_decode_step", "make_prefill_step",
    "make_prefill_chunk_step", "make_serve_decode_step",
    "make_spec_verify_step", "draft_tokens",
    "BatchScheduler", "RequestHandle",
    "TrafficConfig", "TrafficRequest", "generate_workload", "replay",
    "FaultConfig", "FaultEvent", "FaultInjector", "generate_faults",
]
