"""Layered configuration.

Mirrors the reference's config stack (reference: sentinel-core/.../config/
SentinelConfigLoader.java:38-59 — JVM ``-Dcsp.sentinel.*`` > properties
file > defaults; SentinelConfig.java:54-65 for the key set). Here the
layers are: runtime ``set()`` > environment ``SENTINEL_TPU_*`` (or the
reference-compatible ``CSP_SENTINEL_*``) > properties file > defaults.

The properties file path comes from ``SENTINEL_TPU_CONFIG_FILE`` /
``CSP_SENTINEL_CONFIG_FILE`` (reference: SentinelConfigLoader.java:41) or
defaults to ``./sentinel.properties`` if present.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional


def _parse_properties(text: str) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#") or line.startswith("!"):
            continue
        for sep in ("=", ":"):
            if sep in line:
                k, _, v = line.partition(sep)
                out[k.strip()] = v.strip()
                break
    return out


class SentinelConfig:
    """Key registry + typed accessors.

    Key names keep the reference's ``csp.sentinel.*`` spelling
    (reference: SentinelConfig.java:54-65) so existing property files
    carry over.
    """

    APP_NAME = "project.name"
    APP_TYPE = "csp.sentinel.app.type"
    CHARSET = "csp.sentinel.charset"
    SINGLE_METRIC_FILE_SIZE = "csp.sentinel.metric.file.single.size"
    TOTAL_METRIC_FILE_COUNT = "csp.sentinel.metric.file.total.count"
    COLD_FACTOR = "csp.sentinel.flow.cold.factor"
    STATISTIC_MAX_RT = "csp.sentinel.statistic.max.rt"
    SPI_CLASSLOADER = "csp.sentinel.spi.classloader"
    METRIC_FLUSH_INTERVAL = "csp.sentinel.metric.flush.interval"
    # TPU-native keys (no reference equivalent).
    FLUSH_INTERVAL_MS = "sentinel.tpu.flush.interval.ms"
    FLUSH_MAX_BATCH = "sentinel.tpu.flush.max.batch"
    # Max flush_async dispatches in flight before the oldest fetch is
    # forced (bounds device memory held by unfetched results).
    FLUSH_MAX_INFLIGHT = "sentinel.tpu.flush.max.inflight"
    # OccupyTimeoutProperty (reference: CORE/node/OccupyTimeoutProperty.java):
    # max borrowable wait for prioritized entries, < interval.
    OCCUPY_TIMEOUT_MS = "csp.sentinel.statistic.occupy.timeout"
    INITIAL_ROWS = "sentinel.tpu.rows.initial"
    # Host-ingest fast path: persistent param-value intern cache +
    # reusable encode-buffer arena. On by default; the off position
    # exists for differential testing (the with/without smoke test) and
    # as an escape hatch — both paths must produce bit-identical
    # verdicts.
    HOST_FASTPATH = "sentinel.tpu.host.fastpath"
    # Depth-K flush pipeline: Engine.flush() keeps up to this many
    # dispatched-but-unfetched flushes in flight (encode/dispatch of
    # flush N+1 overlaps device execution of flush N). 0 = the fully
    # synchronous flush — the differential oracle for the pipelined
    # path and the default.
    PIPELINE_DEPTH = "sentinel.tpu.host.pipeline.depth"
    # Encode-buffer arena bounds: how many recent padded-shape keys are
    # pooled, and how many buffer sets per key. The per-key bound is
    # raised automatically to pipeline_depth + 1 (every in-flight flush
    # pins one staging set per shape key; an undersized pool would
    # silently fall back to fresh allocations at depth).
    ARENA_MAX_KEYS = "sentinel.tpu.host.arena.max.keys"
    ARENA_PER_KEY = "sentinel.tpu.host.arena.per.key"
    # Engine flight recorder (metrics/telemetry.py): per-flush spans,
    # latency histograms and the blocked-resource sketch. Enabled by
    # default — the off position compiles the kernel sketch fold away
    # and skips every span record, so Engine.last_flush_host_ms (read
    # from the spans) reads zeros (the ≤2% overhead contract is
    # enforced by the telemetry bench test).
    TELEMETRY_ENABLED = "sentinel.tpu.telemetry.enabled"
    TELEMETRY_RING = "sentinel.tpu.telemetry.ring"
    # Device-side top-K blocked-resource candidates folded into each
    # flush's kernel outputs (0 disables the fold entirely) — named
    # apart from the statistics sketch tier (sentinel.tpu.sketch.*).
    TELEMETRY_BLOCKED_TOPK_K = "sentinel.tpu.telemetry.blocked.topk.k"
    # Host-side space-saving summary capacity the per-flush top-Ks
    # merge into.
    TELEMETRY_BLOCKED_TOPK_CAP = "sentinel.tpu.telemetry.blocked.topk.capacity"
    # How many blocked-top-K rows the exports list (Prometheus
    # sentinel_engine_blocked_weight, the `telemetry` command, the
    # sketch tier's candidate listing) when the device fold is off —
    # the ONE home of the former hand-rolled `sketch_k or 10`.
    TELEMETRY_TOPK_EXPORT = "sentinel.tpu.telemetry.topk.export"
    # Statistics sketch tier (runtime/sketch.py): fixed-size on-device
    # count-min + candidate table tracking EVERY key the engine sees
    # (unconfigured/cold resources, high-cardinality param values) with
    # heavy-hitter promotion into exact dense rows. Opt-in — disabled
    # costs one attribute read per submit/flush and the kernel fold is
    # never compiled.
    SKETCH_ENABLED = "sentinel.tpu.sketch.enabled"
    # Count-min geometry: depth hash rows x width counters (width is
    # rounded up to a power of two). Device memory is depth*width*4
    # bytes — O(1) in the key cardinality.
    SKETCH_DEPTH = "sentinel.tpu.sketch.depth"
    SKETCH_WIDTH = "sentinel.tpu.sketch.width"
    # Device candidate-table slots (the space-saving-style heavy-hitter
    # set that rides the coalesced drain fetch).
    SKETCH_CANDIDATES = "sentinel.tpu.sketch.candidates"
    # Decay window: counts halve once per window (engine clock), so a
    # key's steady-state count converges to ~2x its per-window volume.
    SKETCH_WINDOW_MS = "sentinel.tpu.sketch.window.ms"
    # Promotion threshold for sketch-mode param VALUES (estimated
    # acquire/sec; 0 disarms value promotion).
    SKETCH_PROMOTE_QPS = "sentinel.tpu.sketch.promote.qps"
    # Default dense-rule QPS for promoted unconfigured RESOURCES (the
    # synthetic flow rule's count; 0 disarms resource promotion).
    SKETCH_RESOURCE_QPS = "sentinel.tpu.sketch.resource.qps"
    # Max promoted keys (values + resources) held at once.
    SKETCH_PROMOTE_MAX = "sentinel.tpu.sketch.promote.max"
    # Consecutive decay windows a promoted key must stay below the
    # demotion threshold before it falls back to sketch-only.
    SKETCH_DEMOTE_WINDOWS = "sentinel.tpu.sketch.demote.windows"
    # Bound on the host id->name map resolving drained candidate ids
    # back to key names (LRU; ids are stable hashes so eviction never
    # corrupts device state).
    SKETCH_NAMES_CAP = "sentinel.tpu.sketch.names.capacity"
    # Admission tracing (metrics/admission_trace.py): bounded sampled
    # ring of per-admission verdict-provenance records with W3C
    # trace-context propagation. Enabled by default — disabled costs
    # one bool read per submit.
    TRACE_ENABLED = "sentinel.tpu.trace.enabled"
    TRACE_RING = "sentinel.tpu.trace.ring"
    # Head-based probabilistic sample rate (0..1) for admissions with
    # no inbound trace decision; an inbound traceparent's sampled flag
    # is honored as-is.
    TRACE_SAMPLE_RATE = "sentinel.tpu.trace.sample.rate"
    # Always record blocked admissions regardless of the head decision
    # (the "why was THIS call 429'd" mode).
    TRACE_SAMPLE_BLOCKED = "sentinel.tpu.trace.sample.blocked"
    # Per bulk group, at most this many rows recorded per class
    # (blocked / head-sampled) — keeps tracing bounded at bulk sizes.
    TRACE_BULK_CAP = "sentinel.tpu.trace.bulk.cap"
    # Device-failure domain (runtime/failover.py): health state
    # machine + flush watchdog + host-fallback admission + checkpoint/
    # restore. Opt-in — disabled costs one attribute read per flush and
    # device errors re-raise to callers exactly as before.
    FAILOVER_ENABLED = "sentinel.tpu.failover.enabled"
    # Watchdog bound on kernel dispatch and the device->host fetch: a
    # wedged jax.device_get times out (on a waiter thread) and trips
    # the engine DEGRADED instead of stranding submitters forever. Time
    # the waiter spends compiling is not counted against it.
    FAILOVER_FETCH_TIMEOUT_MS = "sentinel.tpu.failover.fetch.timeout.ms"
    # Per-resource fail-open/fail-closed while DEGRADED: "open" |
    # "closed" | "open,resA=closed,..." (first '='-less segment is the
    # default). Default open, like the reference's pass-on-fallback.
    FAILOVER_POLICY = "sentinel.tpu.failover.policy"
    # Host checkpoint cadence in flushes (rides the coalesced result
    # fetch; 0 disables checkpoints — recovery then restores fresh
    # states).
    FAILOVER_CHECKPOINT_EVERY = "sentinel.tpu.failover.checkpoint.every"
    # Consecutive successful probe no-op flushes required before a
    # RECOVERING engine goes HEALTHY.
    FAILOVER_PROBE_FLUSHES = "sentinel.tpu.failover.probe.flushes"
    # Min gap (engine clock) between automatic recovery attempts from
    # the flush path; explicit try_recover() ignores it.
    FAILOVER_RETRY_MS = "sentinel.tpu.failover.retry.ms"
    # Durable checkpoint spill (runtime/failover.py): when set, every
    # stored in-memory checkpoint also spills to this file (atomic
    # rename, versioned header, crc) so a RESTARTED engine process can
    # warm-start via restore_durable(). "" (the default) = off, the
    # pre-PR-15 in-memory-only behavior bit for bit.
    FAILOVER_CKPT_PATH = "sentinel.tpu.failover.checkpoint.path"
    # Min gap between durable spills (wall ms) — bounds the write cost
    # at high flush rates without touching the in-memory cadence.
    FAILOVER_CKPT_INTERVAL_MS = "sentinel.tpu.failover.checkpoint.interval.ms"
    # Max age (wall ms) a durable checkpoint may have at load; older
    # files degrade to a cold start (counted, never an exception).
    # 0 = no age limit (shape/window-geometry validation still applies).
    FAILOVER_CKPT_STALE_MS = "sentinel.tpu.failover.checkpoint.stale.ms"
    # Speculative admission tier (runtime/speculative.py): host mirrors
    # serve the immediate verdict for single entries and bulk groups,
    # the device flush settles authoritatively, and reconciliation at
    # each drain bounds the drift. Opt-in — disabled costs one bool
    # read per entry_sync/submit_bulk.
    SPECULATIVE_ENABLED = "sentinel.tpu.speculative.enabled"
    # Pending-op count at which a speculative entry_sync/submit triggers
    # an async settle dispatch (bounds reconciliation lag without a
    # blocking flush on the admission path).
    SPECULATIVE_FLUSH_BATCH = "sentinel.tpu.speculative.flush.batch"
    # Per-window observed over-admits (speculative admit, device block)
    # after which the tier stops speculating until the window rolls —
    # the divergence safety valve the differential test pins (0 = no
    # enforcement, drift is still measured).
    SPECULATIVE_OVERADMIT_MAX = "sentinel.tpu.speculative.overadmit.max"
    # Drift accounting window (engine clock) for the per-window
    # over/under-admit counters and the drift histogram.
    SPECULATIVE_WINDOW_MS = "sentinel.tpu.speculative.drift.window.ms"
    # Host mirror of the shaping controllers (RateLimiter pacer /
    # WarmUp token ramp): shaped resources get immediate speculative
    # verdicts with exact pacing waits instead of declining to the
    # sync device path. On by default when the tier is on; the off
    # position restores the PR-6 decline-to-device stance.
    SPECULATIVE_SHAPING = "sentinel.tpu.speculative.shaping.enabled"
    # Engine ingest self-protection (runtime/ingest.py): bounded
    # pending-op/bulk queues with a deadline-aware shedding valve.
    # Under saturation callers get a fast BLOCK_SHED verdict instead of
    # unbounded queue growth or indefinite blocking. All three keys
    # default 0 = disarmed (one attribute read per submit).
    INGEST_MAX_PENDING = "sentinel.tpu.ingest.max.pending"
    INGEST_MAX_PENDING_BULK = "sentinel.tpu.ingest.max.pending.bulk"
    # Adapter-edge batch window (runtime/window.py): concurrent
    # in-flight requests from the per-request adapters (WSGI/ASGI/
    # Flask/FastAPI/aiohttp/gRPC/gateway_entry) coalesce for up to
    # window.ms into ONE columnar submit_bulk ride per resource group,
    # with per-request verdict fan-out. 0 (the default) = off: every
    # adapter keeps today's per-request submit+flush behavior.
    INGEST_BATCH_WINDOW_MS = "sentinel.tpu.ingest.batch.window.ms"
    # Max requests one window coalesces before it flushes early.
    INGEST_BATCH_MAX = "sentinel.tpu.ingest.batch.max"
    # Shed when the estimated verdict latency (settle-latency EWMA x
    # (in-flight flushes + 1), the PR-3 flight-recorder signals)
    # exceeds this deadline.
    INGEST_DEADLINE_MS = "sentinel.tpu.ingest.deadline.ms"
    # Self-tuning control plane (runtime/autotune.py): an engine-scoped
    # controller driven once per drain tick that AIMD-adjusts the flush
    # pipeline depth, retunes the adapter batch window, and picks the
    # closed-form vs scan param path from a shape-bucketed cost memo.
    # Default off = bit-identical static-config behavior (one attribute
    # read per drain).
    AUTOTUNE_ENABLED = "sentinel.tpu.autotune.enabled"
    # Decision cadence (engine clock) and per-knob cooldown after a
    # change (hysteresis against oscillation).
    AUTOTUNE_INTERVAL_MS = "sentinel.tpu.autotune.interval.ms"
    AUTOTUNE_COOLDOWN_MS = "sentinel.tpu.autotune.cooldown.ms"
    # Upper bound the depth controller may raise
    # sentinel.tpu.host.pipeline.depth to (never exceeded).
    AUTOTUNE_DEPTH_MAX = "sentinel.tpu.autotune.depth.max"
    # Min settled flush spans per tick before any decision is taken —
    # a thin sample must hold, not steer.
    AUTOTUNE_MIN_FLUSHES = "sentinel.tpu.autotune.min.flushes"
    # Occupancy dead band: raise depth only at >= high, lower only at
    # <= low for idle.ticks consecutive ticks. The gap between the two
    # is the hysteresis band that prevents K <-> K+1 flapping.
    AUTOTUNE_OCC_HIGH = "sentinel.tpu.autotune.occupancy.high"
    AUTOTUNE_OCC_LOW = "sentinel.tpu.autotune.occupancy.low"
    AUTOTUNE_IDLE_TICKS = "sentinel.tpu.autotune.idle.ticks"
    # Device-wait fractions (relative to host encode+dispatch work per
    # tick): raise depth only when unhidden device wait exceeds
    # raise.frac (there is something to hide); treat device wait beyond
    # stall.frac as a drain stall and step depth back down.
    AUTOTUNE_RAISE_FRAC = "sentinel.tpu.autotune.raise.frac"
    AUTOTUNE_STALL_FRAC = "sentinel.tpu.autotune.stall.frac"
    # Batch-window bounds the window controller may grow
    # sentinel.tpu.ingest.batch.{window.ms,max} to.
    AUTOTUNE_WINDOW_MS_MAX = "sentinel.tpu.autotune.window.ms.max"
    AUTOTUNE_WINDOW_BATCH_MAX = "sentinel.tpu.autotune.window.batch.max"
    # Closed-form vs scan param-path cost memo: enabled, exploration
    # samples per (shape bucket, path) before committing, and the
    # relative margin a path must win by before the pick switches.
    AUTOTUNE_PARAM_PATH = "sentinel.tpu.autotune.param.path"
    AUTOTUNE_PARAM_EXPLORE = "sentinel.tpu.autotune.param.explore"
    AUTOTUNE_PARAM_MARGIN = "sentinel.tpu.autotune.param.margin"
    # Bounded decision-log ring (the trajectory the bench stage and the
    # `autotune` command report).
    AUTOTUNE_LOG = "sentinel.tpu.autotune.log"
    # Pre-measured closed-vs-scan param-path timings for the cost memo
    # (tools/k2probe.py --seed-out emits the file): when set, the memo
    # starts COMMITTED to the measured winner per shape bucket instead
    # of exploring each path live. Empty (the default) = explore.
    AUTOTUNE_PARAM_SEED_FILE = "sentinel.tpu.autotune.param.seed.file"
    # Sketch-tier cold-key admission ceiling (runtime/sketch.py):
    # estimated QPS above which an UNPROMOTED sketch-tracked resource
    # (unconfigured or over-cap — today's zero-protection classes) is
    # blocked from the host count-min twin's estimate, closing the gap
    # HashPipe-style heavy-hitter promotion leaves open (a key can burn
    # the full promotion budget's worth of traffic while staying just
    # under every promotion threshold). 0 (the default) = today's
    # cold-pass behavior. The twin is host-side, so the ceiling stays
    # enforced while DEGRADED.
    SKETCH_COLD_QPS = "sentinel.tpu.sketch.cold.qps"
    # Multi-process ingest plane (sentinel_tpu/ipc/): N worker
    # processes encode admissions into a shared-memory MPSC request
    # ring and one engine process drains it onto the columnar
    # submit_bulk spine, fanning verdict frames back through per-worker
    # SPSC response rings. Disabled (the default) = the plane is never
    # constructed, no shared memory exists, and the engine pays at most
    # one attribute read on any hot path.
    IPC_ENABLED = "sentinel.tpu.ipc.enabled"
    # Request-ring geometry: slot count (rounded up to a power of two)
    # and fixed payload bytes per slot (one frame per slot; a frame
    # that cannot fit splits at encode time).
    IPC_RING_SLOTS = "sentinel.tpu.ipc.ring.slots"
    IPC_SLOT_BYTES = "sentinel.tpu.ipc.slot.bytes"
    # Per-worker response-ring slot count (same slot.bytes).
    IPC_RESP_SLOTS = "sentinel.tpu.ipc.response.slots"
    # Worker-slot table size in the control header (max workers that
    # can attach to one plane).
    IPC_WORKERS_MAX = "sentinel.tpu.ipc.workers.max"
    # Worker heartbeat bump cadence, and how stale a worker's heartbeat
    # epoch may go before the plane declares it dead and auto-exits its
    # live THREAD admissions (gauges return to exactly 0).
    IPC_HEARTBEAT_MS = "sentinel.tpu.ipc.heartbeat.ms"
    IPC_WORKER_DEAD_MS = "sentinel.tpu.ipc.worker.dead.ms"
    # How stale the ENGINE heartbeat may go before a worker stops
    # waiting and serves verdicts from the fail-open/closed failover
    # policy snapshot published in the control header.
    IPC_ENGINE_DEAD_MS = "sentinel.tpu.ipc.engine.dead.ms"
    # Death-confirmation grace (ipc/worker.py): with dead.confirm.ms
    # > 0, a stale engine wall clock alone does not flip a worker to
    # the policy path — the worker first re-reads the heartbeat epoch,
    # probes the published engine pid (signal 0) and rings the request
    # doorbell; while the process is provably alive the declaration is
    # deferred up to dead.ms + dead.confirm.ms, so sub-second dead.ms
    # on a pegged-but-alive box does not produce false positives.
    # 0 (the default) keeps the PR-15 wall-staleness predicate exactly.
    IPC_ENGINE_DEAD_CONFIRM_MS = "sentinel.tpu.ipc.engine.dead.confirm.ms"
    # Max time a worker blocks on one verdict before consulting the
    # engine-death path above (bounds a wedged-but-heartbeating engine).
    IPC_TIMEOUT_MS = "sentinel.tpu.ipc.timeout.ms"
    # Drainer idle poll floor, microseconds (the plane backs off toward
    # this when the request ring runs empty; "sleep" wakeup mode only).
    IPC_POLL_US = "sentinel.tpu.ipc.poll.us"
    # Worker-side micro-window (ipc/worker.py): concurrent
    # entry/bulk/exit calls on one IngestClient coalesce into ONE
    # columnar frame per bounded window — the client-side twin of the
    # adapter batch window (runtime/window.py) — amortizing ring
    # claims, intern lookups, publishes and wakeups under concurrency.
    # window.ms 0 (the default) keeps per-call framing exactly;
    # window.max caps rows per window (flush-on-size).
    IPC_CLIENT_WINDOW_MS = "sentinel.tpu.ipc.client.window.ms"
    IPC_CLIENT_WINDOW_MAX = "sentinel.tpu.ipc.client.window.max"
    # Ring wakeup strategy for the plane drainer and the worker reader
    # threads: "sleep" (the default — fixed sleep-poll backoff) or
    # "adaptive" (bounded spin for spin.us, then park on a
    # shared-memory doorbell semaphore with an exponentially growing
    # timeout capped at park.ms — cuts the round-trip floor without
    # burning a core when idle; the producer rings the doorbell only
    # when the consumer is parked). spin.us -1 (the default) auto-picks
    # 0 on <=2-core hosts (spinning steals the core the OTHER side of
    # the pipe needs — measured 2x WORSE than pure park on the 1-core
    # box) and 50 on larger hosts where a published frame usually lands
    # within the spin.
    IPC_WAKEUP = "sentinel.tpu.ipc.wakeup"
    IPC_WAKEUP_SPIN_US = "sentinel.tpu.ipc.wakeup.spin.us"
    IPC_WAKEUP_PARK_MS = "sentinel.tpu.ipc.wakeup.park.ms"
    # Worker mode (ipc/worker_mode.py): route this process's api.entry
    # surface — entry/try_entry/entry_async/entry_windowed(_async), and
    # therefore every adapter — through its attached IngestClient
    # instead of a local engine, making a gunicorn-style N-process
    # deployment one line (api.run_workers / tools/ipc_launch.py).
    IPC_WORKER_MODE = "sentinel.tpu.ipc.worker.mode"
    # Engine hot-restart (ipc/supervise.py, PR 15). shm.prefix names
    # the plane's shared-memory segments deterministically
    # ("<prefix>-ctl" / "-req" / "-resp<N>") so a RESTARTED engine
    # process re-attaches to the EXISTING rings instead of creating
    # fresh anonymous ones; "" (the default) keeps the anonymous
    # PR-13/14 segments exactly.
    IPC_SHM_PREFIX = "sentinel.tpu.ipc.shm.prefix"
    # Worker reconnect: when the control header's engine-boot epoch
    # bumps (a new engine attached to the rings), workers re-intern,
    # re-assert their live-admission ledgers and replay completions
    # buffered during the dead window (up to reconnect.exits.max;
    # overflow drops oldest, counted in exits_dropped). Off restores
    # the PR-14 stance: engine death permanently drops undeliverable
    # completions and a returning engine starts with empty ledgers.
    IPC_RECONNECT = "sentinel.tpu.ipc.reconnect.enabled"
    IPC_RECONNECT_EXITS_MAX = "sentinel.tpu.ipc.reconnect.exits.max"
    # Planned live handoff (ipc/plane.py handoff() + supervise.py):
    # how long a worker HOLDS a new admission when the control header
    # publishes HANDOFF (old engine draining) before giving up and
    # serving the failover policy snapshot. The hold also covers the
    # detach->successor-attach gap, so an orderly config-push handoff
    # serves ZERO policy verdicts.
    IPC_HANDOFF_WAIT_MS = "sentinel.tpu.ipc.handoff.wait.ms"
    # Engine supervision (ipc/supervise.py run_engine_supervised /
    # tools/ipc_launch.py --supervise): restart backoff (shared
    # datasource Backoff shape: capped exponential) and a restart
    # budget (0 = unlimited).
    SUPERVISE_BACKOFF_MS = "sentinel.tpu.supervise.backoff.ms"
    SUPERVISE_BACKOFF_MAX_MS = "sentinel.tpu.supervise.backoff.max.ms"
    SUPERVISE_RESTARTS_MAX = "sentinel.tpu.supervise.restarts.max"
    # Warm standby (ipc/supervise.py): pre-fork a SECOND engine child
    # that imports JAX, loads rules, warm-compiles the flush kernels
    # via probe batches and re-warms from the durable checkpoint every
    # warm.interval.ms — parked WITHOUT attaching to the rings. On
    # primary death (or planned handoff) it attaches immediately,
    # cutting the outage from cold-boot seconds to the detection
    # window; the supervisor pre-forks the next standby right after.
    # Off (the default) keeps PR-15 cold-respawn supervision exactly.
    SUPERVISE_STANDBY = "sentinel.tpu.supervise.standby.enabled"
    SUPERVISE_STANDBY_WARM_MS = "sentinel.tpu.supervise.standby.warm.interval.ms"
    # Per-resource provenance metric plane (metrics/provenance.py):
    # (second, resource) speculative/degraded/shed/drift ledger drained
    # into MetricNodeLine v2 columns and the bounded
    # sentinel_resource_* Prometheus export. Enabled by default —
    # disabled costs one bool read per call site.
    RESOURCE_METRICS_ENABLED = "sentinel.tpu.metrics.resource.enabled"
    # Cardinality bound of the ledger: resources past this fold into
    # the __other__ row (the export is additionally bounded by the
    # blocked top-K sketch + configured resources).
    RESOURCE_METRICS_CAP = "sentinel.tpu.metrics.resource.capacity"
    # Batched cluster token plane (cluster/{protocol,client,server}.py).
    # window.ms > 0 turns on the client-side micro-window: concurrent
    # per-op token requests coalesce under the client lock into one
    # FLOW_REQUEST_BATCH frame (flushed after window.ms or at
    # window.max rows, whichever first), xid-multiplexed on the reader
    # so windows pipeline without waiting for earlier responses.
    # window.ms 0 (the default) keeps per-call framing exactly.
    CLUSTER_CLIENT_WINDOW_MS = "sentinel.tpu.cluster.client.window.ms"
    CLUSTER_CLIENT_WINDOW_MAX = "sentinel.tpu.cluster.client.window.max"
    # Local quota leases: with lease.enabled the server may attach a
    # lease (N tokens, valid lease.ttl.ms from receipt) to a batch
    # response for a flow that was hot in that frame (≥ lease.min.batch
    # admitted rows); the grant is lease.frac of the flow's remaining
    # headroom capped at lease.max tokens, debited from the server
    # window UP FRONT (never over-admits globally; unused remainder is
    # forfeited, not credited back). The client then admits that flow
    # locally with zero RPCs until the lease drains or expires, and
    # reports consumption on its next batch frame. Off (the default)
    # grants nothing and the client stance is bit-identical to per-call.
    CLUSTER_LEASE_ENABLED = "sentinel.tpu.cluster.lease.enabled"
    CLUSTER_LEASE_MIN_BATCH = "sentinel.tpu.cluster.lease.min.batch"
    CLUSTER_LEASE_FRAC = "sentinel.tpu.cluster.lease.frac"
    CLUSTER_LEASE_MAX = "sentinel.tpu.cluster.lease.max"
    CLUSTER_LEASE_TTL_MS = "sentinel.tpu.cluster.lease.ttl.ms"
    # Cap on the TOTAL milliseconds one op batch may sleep honoring
    # SHOULD_WAIT verdicts (prioritized occupy-style pacing); overflow
    # is forfeited and the op proceeds. The pre-cap behavior slept
    # per-op back-to-back, unbounded.
    CLUSTER_WAIT_CAP_MS = "sentinel.tpu.cluster.wait.cap.ms"
    # Sharded token plane (cluster/shards.py): shards > 1 partitions
    # token state across N token servers by flow-id hash
    # (shard = crc32(flow_id) % shards). shards.map is the endpoint
    # list, CSV "host:port,host:port,..." with at least `shards`
    # entries; shards.map.version is bumped by the operator on every
    # map edit — clients compare it per batch and rebuild their
    # connections when it moves. shards=1 (the default) keeps the
    # single-server PR-16 client byte-identical.
    CLUSTER_SHARDS = "sentinel.tpu.cluster.shards"
    CLUSTER_SHARDS_MAP = "sentinel.tpu.cluster.shards.map"
    CLUSTER_SHARDS_MAP_VERSION = "sentinel.tpu.cluster.shards.map.version"
    # Sketch gossip (cluster/gossip.py): engines exchange their host
    # count-min twin + candidate tables (SKETCH_PUSH/SKETCH_MERGED) so
    # heavy hitters are detected fleet-wide. enabled arms the host twin
    # and the fleet-view evaluation; port is this engine's gossip
    # listener (0 = ephemeral); peers is CSV "host:port,..." of other
    # engines' listeners; interval.ms > 0 starts a pusher thread (0 =
    # manual rounds only); stale.windows bounds how many decay windows
    # a remote snapshot outlives its last push before it is dropped.
    GOSSIP_ENABLED = "sentinel.tpu.gossip.enabled"
    GOSSIP_PORT = "sentinel.tpu.gossip.port"
    GOSSIP_PEERS = "sentinel.tpu.gossip.peers"
    GOSSIP_INTERVAL_MS = "sentinel.tpu.gossip.interval.ms"
    GOSSIP_STALE_WINDOWS = "sentinel.tpu.gossip.stale.windows"
    # Fleet span journal (metrics/spans.py): per-process bounded ring
    # of wall-clock admission spans (worker join->verdict, engine
    # frame drain, cluster RPC, shard serve) with rolling jsonl spill
    # for tools/fleetdump.py to merge into one Perfetto timeline.
    # Off by default — disabled costs one bool read per call site and
    # verdicts are bit-identical either way.
    SPANS_ENABLED = "sentinel.tpu.spans.enabled"
    # Bounded in-memory ring per process (oldest spans drop first).
    SPANS_RING = "sentinel.tpu.spans.ring"
    # Journal spill directory ("" = the metric log dir). Files are
    # named {app}-spans-{role}-{pid}.jsonl, size-rolled to one .1
    # backup like the metric log.
    SPANS_DIR = "sentinel.tpu.spans.dir"
    # Spill to the journal file automatically once this many spans
    # accumulate since the last spill (0 = only explicit/close spills).
    SPANS_SPILL_EVERY = "sentinel.tpu.spans.spill.every"
    # Black-box flight recorder (runtime/capture.py): bounded rolling
    # on-disk capture of the columnar admission stream in the
    # ipc/frames.py codec, replayable bit-exactly by tools/replay.py.
    # Off by default — the disabled footprint is one attribute read per
    # flush and verdicts are bit-identical either way.
    CAPTURE_ENABLED = "sentinel.tpu.capture.enabled"
    # Segment directory ("" = ./sentinel-capture).
    CAPTURE_DIR = "sentinel.tpu.capture.dir"
    # Rollover size per segment file and the live (rollover-eligible)
    # segment count bound; oldest live segments are deleted past it.
    CAPTURE_SEGMENT_BYTES = "sentinel.tpu.capture.segment.bytes"
    CAPTURE_SEGMENTS_MAX = "sentinel.tpu.capture.segments.max"
    # Postmortem freeze: segments whose last record is younger than
    # freeze.seconds are renamed frozen-* (pinned against rollover) on
    # a breaker opening, a DEGRADED transition, a shed streak of
    # freeze.shed.streak consecutive valve sheds, the `capture`
    # transport command, or (next boot) engine death. frozen.max bounds
    # the pinned set, oldest deleted first.
    CAPTURE_FREEZE_SECONDS = "sentinel.tpu.capture.freeze.seconds"
    CAPTURE_FROZEN_MAX = "sentinel.tpu.capture.frozen.max"
    CAPTURE_SHED_STREAK = "sentinel.tpu.capture.freeze.shed.streak"
    LOG_DIR = "csp.sentinel.log.dir"

    DEFAULTS: Dict[str, str] = {
        APP_TYPE: "0",
        CHARSET: "utf-8",
        SINGLE_METRIC_FILE_SIZE: str(1024 * 1024 * 50),
        TOTAL_METRIC_FILE_COUNT: "6",
        COLD_FACTOR: "3",
        STATISTIC_MAX_RT: "4900",  # reference: SentinelConfig.java DEFAULT_STATISTIC_MAX_RT
        METRIC_FLUSH_INTERVAL: "1",
        FLUSH_INTERVAL_MS: "2",
        FLUSH_MAX_BATCH: "131072",
        FLUSH_MAX_INFLIGHT: "2",
        INITIAL_ROWS: "1024",
        OCCUPY_TIMEOUT_MS: "500",
        HOST_FASTPATH: "true",
        PIPELINE_DEPTH: "0",
        ARENA_MAX_KEYS: "8",
        ARENA_PER_KEY: "4",
        TELEMETRY_ENABLED: "true",
        TELEMETRY_RING: "4096",
        TELEMETRY_BLOCKED_TOPK_K: "8",
        TELEMETRY_BLOCKED_TOPK_CAP: "64",
        TELEMETRY_TOPK_EXPORT: "10",
        SKETCH_ENABLED: "false",
        SKETCH_DEPTH: "4",
        SKETCH_WIDTH: "2048",
        SKETCH_CANDIDATES: "64",
        SKETCH_WINDOW_MS: "1000",
        SKETCH_PROMOTE_QPS: "0",
        SKETCH_RESOURCE_QPS: "0",
        SKETCH_PROMOTE_MAX: "64",
        SKETCH_DEMOTE_WINDOWS: "3",
        SKETCH_NAMES_CAP: "65536",
        SKETCH_COLD_QPS: "0",
        TRACE_ENABLED: "true",
        TRACE_RING: "2048",
        TRACE_SAMPLE_RATE: "0.01",
        TRACE_SAMPLE_BLOCKED: "true",
        TRACE_BULK_CAP: "4",
        FAILOVER_ENABLED: "false",
        FAILOVER_FETCH_TIMEOUT_MS: "5000",
        FAILOVER_POLICY: "open",
        FAILOVER_CHECKPOINT_EVERY: "8",
        FAILOVER_PROBE_FLUSHES: "3",
        FAILOVER_RETRY_MS: "1000",
        FAILOVER_CKPT_PATH: "",
        FAILOVER_CKPT_INTERVAL_MS: "1000",
        FAILOVER_CKPT_STALE_MS: "0",
        SPECULATIVE_ENABLED: "false",
        SPECULATIVE_FLUSH_BATCH: "64",
        SPECULATIVE_OVERADMIT_MAX: "64",
        SPECULATIVE_WINDOW_MS: "1000",
        SPECULATIVE_SHAPING: "true",
        INGEST_MAX_PENDING: "0",
        INGEST_MAX_PENDING_BULK: "0",
        INGEST_DEADLINE_MS: "0",
        INGEST_BATCH_WINDOW_MS: "0",
        INGEST_BATCH_MAX: "256",
        RESOURCE_METRICS_ENABLED: "true",
        RESOURCE_METRICS_CAP: "256",
        AUTOTUNE_ENABLED: "false",
        AUTOTUNE_INTERVAL_MS: "250",
        AUTOTUNE_COOLDOWN_MS: "1000",
        AUTOTUNE_DEPTH_MAX: "4",
        AUTOTUNE_MIN_FLUSHES: "8",
        AUTOTUNE_OCC_HIGH: "0.85",
        AUTOTUNE_OCC_LOW: "0.2",
        AUTOTUNE_IDLE_TICKS: "3",
        AUTOTUNE_RAISE_FRAC: "0.1",
        AUTOTUNE_STALL_FRAC: "2.0",
        AUTOTUNE_WINDOW_MS_MAX: "20",
        AUTOTUNE_WINDOW_BATCH_MAX: "4096",
        AUTOTUNE_PARAM_PATH: "true",
        AUTOTUNE_PARAM_EXPLORE: "3",
        AUTOTUNE_PARAM_MARGIN: "0.15",
        AUTOTUNE_PARAM_SEED_FILE: "",
        AUTOTUNE_LOG: "256",
        IPC_ENABLED: "false",
        IPC_RING_SLOTS: "1024",
        IPC_SLOT_BYTES: "16384",
        IPC_RESP_SLOTS: "1024",
        IPC_WORKERS_MAX: "8",
        IPC_HEARTBEAT_MS: "100",
        IPC_WORKER_DEAD_MS: "1000",
        IPC_ENGINE_DEAD_MS: "1000",
        IPC_ENGINE_DEAD_CONFIRM_MS: "0",
        IPC_TIMEOUT_MS: "5000",
        IPC_POLL_US: "200",
        IPC_CLIENT_WINDOW_MS: "0",
        IPC_CLIENT_WINDOW_MAX: "256",
        IPC_WAKEUP: "sleep",
        IPC_WAKEUP_SPIN_US: "-1",
        IPC_WAKEUP_PARK_MS: "5",
        IPC_WORKER_MODE: "false",
        IPC_SHM_PREFIX: "",
        IPC_RECONNECT: "true",
        IPC_RECONNECT_EXITS_MAX: "4096",
        IPC_HANDOFF_WAIT_MS: "3000",
        SUPERVISE_BACKOFF_MS: "500",
        SUPERVISE_BACKOFF_MAX_MS: "10000",
        SUPERVISE_RESTARTS_MAX: "0",
        SUPERVISE_STANDBY: "false",
        SUPERVISE_STANDBY_WARM_MS: "2000",
        CLUSTER_CLIENT_WINDOW_MS: "0",
        CLUSTER_CLIENT_WINDOW_MAX: "128",
        CLUSTER_LEASE_ENABLED: "false",
        CLUSTER_LEASE_MIN_BATCH: "4",
        CLUSTER_LEASE_FRAC: "0.5",
        CLUSTER_LEASE_MAX: "256",
        CLUSTER_LEASE_TTL_MS: "100",
        CLUSTER_WAIT_CAP_MS: "1000",
        CLUSTER_SHARDS: "1",
        CLUSTER_SHARDS_MAP: "",
        CLUSTER_SHARDS_MAP_VERSION: "0",
        GOSSIP_ENABLED: "false",
        GOSSIP_PORT: "0",
        GOSSIP_PEERS: "",
        GOSSIP_INTERVAL_MS: "0",
        GOSSIP_STALE_WINDOWS: "4",
        SPANS_ENABLED: "false",
        SPANS_RING: "8192",
        SPANS_DIR: "",
        SPANS_SPILL_EVERY: "0",
        CAPTURE_ENABLED: "false",
        CAPTURE_DIR: "",
        CAPTURE_SEGMENT_BYTES: "4194304",
        CAPTURE_SEGMENTS_MAX: "8",
        CAPTURE_FREEZE_SECONDS: "30",
        CAPTURE_FROZEN_MAX: "16",
        CAPTURE_SHED_STREAK: "64",
    }

    def __init__(self, load_env: bool = True, config_file: Optional[str] = None) -> None:
        self._lock = threading.RLock()
        self._runtime: Dict[str, str] = {}
        self._file: Dict[str, str] = {}
        self._env: Dict[str, str] = {}
        if load_env:
            self._load_file(config_file)
            self._load_env()

    def _load_file(self, config_file: Optional[str]) -> None:
        path = (
            config_file
            or os.environ.get("SENTINEL_TPU_CONFIG_FILE")
            or os.environ.get("CSP_SENTINEL_CONFIG_FILE")
            or "sentinel.properties"
        )
        try:
            with open(path, "r", encoding="utf-8") as f:
                self._file = _parse_properties(f.read())
        except OSError:
            self._file = {}

    def _load_env(self) -> None:
        # Accept each key upper-cased with dots as underscores — both the
        # exact form (CSP_SENTINEL_FLOW_COLD_FACTOR, PROJECT_NAME) and a
        # SENTINEL_TPU_-prefixed form for keys not already namespaced.
        for key in list(self.DEFAULTS) + [self.APP_NAME, self.LOG_DIR]:
            env_key = key.replace(".", "_").upper()
            candidates = [env_key]
            if not env_key.startswith(("CSP_", "SENTINEL_TPU_")):
                candidates.append("SENTINEL_TPU_" + env_key)
            for cand in candidates:
                v = os.environ.get(cand)
                if v is not None:
                    self._env[key] = v
                    break

    def get(self, key: str, default: Optional[str] = None) -> Optional[str]:
        with self._lock:
            for layer in (self._runtime, self._env, self._file):
                if key in layer:
                    return layer[key]
        if key in self.DEFAULTS:
            return self.DEFAULTS[key]
        return default

    def set(self, key: str, value: str) -> None:
        with self._lock:
            self._runtime[key] = str(value)

    def set_if_absent(self, key: str, value: str) -> None:
        with self._lock:
            if self.get(key) is None:
                self._runtime[key] = str(value)

    def runtime_snapshot(self, prefix: str = "") -> Dict[str, str]:
        """Copy of the runtime-set keys (``config.set``) under a prefix
        — what a spawned worker process replays so it sees this
        process's runtime config (spawn children start from defaults +
        env, not from the parent's runtime layer)."""
        with self._lock:
            return {
                k: v for k, v in self._runtime.items()
                if k.startswith(prefix)
            }

    def get_int(self, key: str, default: int = 0) -> int:
        v = self.get(key)
        try:
            return int(v) if v is not None else default
        except ValueError:
            return default

    def get_float(self, key: str, default: float = 0.0) -> float:
        v = self.get(key)
        try:
            return float(v) if v is not None else default
        except ValueError:
            return default

    def get_bool(self, key: str, default: bool = False) -> bool:
        v = self.get(key)
        if v is None:
            return default
        return v.strip().lower() in ("1", "true", "yes", "on")

    # --- commonly used typed views (reference: SentinelConfig.java) ---
    @property
    def app_name(self) -> str:
        return self.get(self.APP_NAME) or "sentinel-tpu-app"

    @property
    def cold_factor(self) -> int:
        # Reference clamps coldFactor <= 1 back to 3 (SentinelConfig#coldFactor).
        v = self.get_int(self.COLD_FACTOR, 3)
        return 3 if v <= 1 else v

    @property
    def statistic_max_rt(self) -> int:
        return self.get_int(self.STATISTIC_MAX_RT, 4900)

    @property
    def occupy_timeout_ms(self) -> int:
        # Clamped to the statistic interval like OccupyTimeoutProperty
        # (a wait beyond one interval can never be satisfied).
        from sentinel_tpu.models import constants as C

        v = self.get_int(self.OCCUPY_TIMEOUT_MS, 500)
        return max(0, min(v, C.DEFAULT_WINDOW_INTERVAL_MS))

    def reset(self) -> None:
        with self._lock:
            self._runtime.clear()


config = SentinelConfig()
