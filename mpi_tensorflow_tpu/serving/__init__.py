"""Continuous-batching serving subsystem (Orca / vLLM lineage).

Six cooperating layers, host-side policy over device-side math:

- ``paged_cache``  — fixed device pool of KV blocks + the refcounted
                     host block allocator; memory scales with LIVE
                     tokens, not ``batch x max_len`` (vs
                     models/gpt.init_cache), and refcounts let one
                     physical block back many sequences.
- ``prefix_cache`` — radix trie over full prompt blocks (RadixAttention
                     lineage): new requests map already-cached prefix
                     blocks instead of recomputing them, with
                     copy-on-write on divergence and LRU eviction of
                     unreferenced entries under pool pressure.
- ``scheduler``    — request queue, admit-on-free-blocks, per-step slot
                     recycling on EOS/budget, eviction under pressure;
                     admission control (feasibility check, bounded
                     queue, deadlines), livelock/starvation guards, and
                     a structured terminal status for every request.
- ``speculative``  — speculative-decoding drafters (Leviathan et al.
                     lineage): an n-gram self-draft and a tiny-model
                     drafter over its own paged pool propose k tokens
                     that the engine verifies in ONE batched forward,
                     accepting the longest argmax-matching prefix —
                     greedy outputs stay token-identical by
                     construction while one KV-streaming pass covers
                     up to k+1 emitted tokens.
- ``engine``       — chunked prefill + single-token decode (or
                     (k+1)-token speculative verify) steps at a small
                     fixed set of bucketed shapes (powers of two),
                     with the block pool donated through every dispatch
                     so steady-state serving updates the cache in place
                     and never recompiles after bucket warmup; graceful
                     SIGTERM drain via train/preemption.PreemptionGuard.
- ``iteration``    — THE shared per-iteration serving body (submit
                     stamping, deadline sweep, latency cadence,
                     eviction discard, journal wiring) both
                     ``engine.run`` and the router's replicas drive —
                     guard/journal/drain semantics live in exactly one
                     place.
- ``recovery``     — host-side replay journal (prompt + generated
                     prefix per request) and the transient-failure
                     supervisor: rebuild pools/engine on device loss and
                     replay live sequences token-identically (greedy
                     decode is deterministic); plus the fleet journal
                     merge/replay helpers the router's failover uses.
- ``tp``           — tensor parallelism for the engine: shard the
                     pool (by head), QKV/O projections, and MLP over a
                     ``tp`` mesh axis via shard_map (one psum per
                     row-parallel output); block tables replicate, so
                     every host-side layer above stays tp-unaware.
- ``loadgen``      — trace-driven load generation: a seeded
                     ``WorkloadSpec`` builds the synthetic request
                     trace (Poisson / bursty MMPP / diurnal /
                     multi-tenant arrivals, heavy-tailed lengths,
                     shared prefixes, per-request SLO deadlines, sticky
                     sessions) — (spec, seed) reproduces the identical
                     trace across runs, A/B arms, and replay.
- ``autoscale``    — advisory replica auto-scaling: a ``ScaleAdvisor``
                     folds the scheduler/router load signals (queue
                     depth, occupancy, shed rate) into per-tick
                     scale-up/down advice under hysteresis + cooldown,
                     recorded in the run's result; with tracing on it
                     consumes the SAME ``TraceBuffer`` step records the
                     trace exports, so advice is explainable from the
                     trace.
- ``tracing``      — host-side request-lifecycle spans (arrive/queued/
                     admitted/prefill chunks/first token/decode/
                     terminal, plus eviction and failover-migration
                     transitions) and a bounded per-step phase timeline
                     (``TraceBuffer``), fleet-merged across replicas
                     and incarnations; exports Chrome trace-event JSON
                     and the ``breakdown`` block.  Off = no
                     tracer object, byte-for-byte untraced; on = host
                     clocks only, zero device syncs.
- ``router``       — data-parallel scale-out WITH fleet fault
                     tolerance: N whole engine replicas (each with its
                     own replay journal) behind session-affinity +
                     health-gated least-load placement; a failed
                     replica's live work migrates to survivors by
                     journal-prefix replay (token-identical), a
                     per-replica circuit breaker ejects/probes/readmits
                     on capped exponential backoff, and SIGTERM drains
                     the whole fleet.

The decode math itself lives in models/gpt.CausalLm.forward_paged (the
shared transformer stack) and ops/paged_attention (gather/scatter).
"""

from mpi_tensorflow_tpu.serving.engine import (  # noqa: F401
    PagedDecodeEngine, ServeConfig)
from mpi_tensorflow_tpu.serving.paged_cache import (  # noqa: F401
    BlockAllocator, init_pools)
from mpi_tensorflow_tpu.serving.prefix_cache import (  # noqa: F401
    PrefixCache)
from mpi_tensorflow_tpu.serving.iteration import (  # noqa: F401
    DrainTracker, EngineLoop)
from mpi_tensorflow_tpu.serving.recovery import (  # noqa: F401
    ReplayJournal, fleet_outputs, fleet_replay_requests, fleet_statuses,
    run_with_replay)
from mpi_tensorflow_tpu.serving.router import (  # noqa: F401
    FaultPlan, ReplicaFault, ReplicaRouter)
from mpi_tensorflow_tpu.serving.scheduler import (  # noqa: F401
    Request, RejectedRequest, Scheduler, TERMINAL_STATUSES)
from mpi_tensorflow_tpu.serving.speculative import (  # noqa: F401
    Drafter, DraftModelDrafter, NgramDrafter, make_drafter)
from mpi_tensorflow_tpu.serving.loadgen import (  # noqa: F401
    LENGTH_DISTS, TenantClass, Trace, WORKLOADS, WorkloadSpec,
    build_trace, default_tenants, per_request_rows)
from mpi_tensorflow_tpu.serving.autoscale import (  # noqa: F401
    ScaleAdvisor, ScalePolicy)
from mpi_tensorflow_tpu.serving.tracing import (  # noqa: F401
    EngineTracer, Span, TraceBuffer, merge_spans, write_chrome_trace)
