"""Global runtime configuration.

Mirrors the reference's two-tier config (core/global.hpp:29-124, core/config.hpp:42-235):
key-value settings loaded from a config file or string, split into settings that are
immutable after boot and settings that can be reloaded at runtime via the console
``config -s`` command (config.hpp:183-198). Derived invariants are recomputed on every
load (config.hpp:220-235).

TPU-specific additions replace the RDMA/GPU knobs: device-engine enablement, binding
table capacity classes, and all-to-all shuffle capacities.
"""

from __future__ import annotations

from dataclasses import dataclass, field, fields


@dataclass
class GlobalConfig:
    # ---- immutable after boot (config.hpp:42-110) ----
    num_workers: int = 1  # graph partitions (reference: num_servers)
    num_proxies: int = 1
    num_engines: int = 4  # host executor threads per worker
    input_folder: str = ""
    memstore_size_gb: int = 4
    est_bdr_threshold: int = 0  # reserved (reference RDMA buffer sizing)
    enable_tpu: bool = True  # accelerator engine on (reference: USE_GPU path)
    enable_merge_join: bool = True  # sort-merge batch chains (gather-free v2)
    # HBM segment-cache budget (reference: gpu_kvcache). Conservative default:
    # heavy-chain buffers at 32M-row capacity classes can hold several GiB
    # live while dispatches pipeline — leave most of the 16 GiB to chain
    # buffers.
    tpu_mem_cache_gb: int = 4
    enable_dynamic_store: bool = False  # append-only delta segments
    enable_versatile: bool = True  # variable-predicate support (USE_VERSATILE)

    # ---- mutable at runtime (config.hpp:112-151) ----
    enable_planner: bool = True
    # skip execution when the planner proves the result empty from exact
    # stats (planner.hpp:1505-1509 is_empty). Off => the full chain runs.
    enable_empty_shortcircuit: bool = True
    enable_vattr: bool = False  # attribute-triple queries
    enable_corun: bool = False
    silent: bool = True  # blind mode: don't ship result tables to the proxy
    mt_threshold: int = 8  # max fan-out slices for heavy index-origin queries
    rdma_threshold: int = 300  # rows >= threshold -> fork-join (dist shuffle)
    # owner-routed in-place execution for small-table distributed chains
    # (reference need_fork_join, sparql.hpp:802-814 + proxy owner routing,
    # proxy.hpp:201-219): a chain whose live table stays under this many
    # rows runs host-side with per-row owner-routed reads and ZERO
    # collectives; growing past it aborts back to the collective path.
    # Scaled above rdma_threshold because the single-driver "one-sided
    # read" is a host array access, far cheaper than an RDMA round trip.
    enable_dist_inplace: bool = True
    dist_inplace_rows: int = 16384
    stealing_pattern: int = 0  # 0: pair, 1: ring (host engine work stealing)
    enable_budget: bool = True
    gpu_enable_pipeline: bool = True  # prefetch next pattern's segments to HBM
    enable_fp_probe: bool = True  # fingerprint-packed hash probe (XLA path)
    # Pallas streaming merge-expand for dense heavy expansions (tpu_stream)
    enable_stream_expand: bool = True

    # ---- resilience knobs (runtime/resilience.py; all mutable) ----
    # per-query wall-clock deadline in ms; 0 disables. Checked at every BGP
    # step / chain attempt; expiry raises a structured QueryTimeout and the
    # reply carries a partial result (result.complete = False).
    query_deadline_ms: int = 0
    # per-query intermediate-row work budget; 0 disables. Every BGP step
    # charges its output rows; overrun raises BudgetExceeded. This is the
    # blowup guard GPU-side Datalog engines use instead of OOMing.
    query_budget_rows: int = 0
    # on deadline/budget expiry keep the rows produced so far and tag the
    # reply incomplete instead of clearing the table
    enable_partial_results: bool = True
    # transient-failure retry (shard fetch, HDFS reads, chain dispatch):
    # attempts, exponential-backoff base, and backoff ceiling
    retry_max_attempts: int = 3
    retry_base_ms: int = 10
    retry_max_ms: int = 2000
    # per-shard circuit breaker: consecutive failures before the breaker
    # opens, and how long it stays open before a half-open trial
    breaker_threshold: int = 3
    breaker_cooldown_ms: int = 5000

    # ---- fault tolerance / durability (store/wal.py, runtime/recovery.py,
    # parallel/sharded_store.py replication) ----
    # how many hosts hold each logical shard's data: 1 = no replication
    # (today's behavior); k > 1 mirrors every shard onto its k-1 successor
    # hosts, and a failed primary fetch transparently fails over to a
    # replica instead of substituting an empty shard (results stay
    # complete=True while any replica survives). Immutable: replicas are
    # cloned when the sharded store is built.
    replication_factor: int = 1
    # write-ahead log for mutations (dynamic inserts + stream epochs):
    # "" disables (default — the mutation hooks degrade to one str check).
    # Records are length-prefixed + CRC-checksummed, appended BEFORE the
    # mutation is acknowledged, rotated at wal_segment_mb, and truncated
    # behind checkpoints.
    wal_dir: str = ""
    # fsync policy: none (OS buffering), interval (at most once per
    # wal_sync_interval_s), always (every append — the durability of a
    # classic redo log, at fsync cost per batch)
    wal_sync: str = "none"
    wal_sync_interval_s: int = 1
    wal_segment_mb: int = 64
    # crash-consistent checkpoints (base partitions + dynamic deltas +
    # stream registry/window state): directory ("" = off) and the periodic
    # checkpointer cadence (0 = manual `checkpoint` console verb only)
    checkpoint_dir: str = ""
    checkpoint_interval_s: int = 0
    # ---- multi-process data plane (runtime/transport.py + procs.py) ----
    # transport seam for shard fetches / migration transfers: "loopback"
    # executes ops in-process against the local store (byte-for-byte the
    # single-process behavior, zero serialization); "socket" arms the
    # framed TCP wire path whose peers the process supervisor registers.
    transport_mode: str = "loopback"
    # per-connection send/recv and connect timeouts for the socket
    # transport; a timeout surfaces as TransientFault → retry_call →
    # breaker, never a hung query
    transport_timeout_ms: int = 2000
    transport_connect_timeout_ms: int = 1000
    # hard ceiling on one wire frame, enforced on BOTH encode and decode
    # (oversized payloads raise FRAME_TOO_LARGE naming this knob)
    transport_max_frame_mb: int = 64
    # process supervision: worker processes per parent (shards are split
    # into contiguous groups), heartbeat cadence and the consecutive-miss
    # threshold that declares a worker dead, and the capped-exponential
    # restart backoff (base * 2^n, clamped to the max)
    proc_workers: int = 2
    proc_heartbeat_ms: int = 500
    proc_heartbeat_misses: int = 3
    proc_restart_backoff_ms: int = 100
    proc_restart_backoff_max_ms: int = 5000

    # ---- observability knobs (wukong_tpu/obs/; all mutable) ----
    # per-query tracing (trace id + span stack, proxy->engine->shard-fetch).
    # Off by default: every hook degrades to one getattr/None check, so the
    # bench hot path is unchanged (guarded by the PR's before/after number).
    enable_tracing: bool = False
    # sample 1 in N queries when tracing is enabled (1 = every query)
    trace_sample_every: int = 1
    # flight recorder: completed traces kept in the bounded ring
    trace_ring: int = 64
    # always-on slow-query log: a traced query slower than this dumps its
    # full trace (0 disables the threshold; resilience-failure codes
    # QUERY_TIMEOUT/BUDGET_EXCEEDED/SHARD_UNAVAILABLE always dump)
    trace_slow_ms: int = 1000
    # directory for JSON trace dumps ("" = in-memory only; the
    # WUKONG_TRACE_DIR env var is the out-of-band override)
    trace_dump_dir: str = ""
    # HTTP scrape endpoint for render_prometheus() (GET /metrics; JSON
    # snapshot at /metrics.json). 0 = off (default). The server runs on a
    # stdlib http.server daemon thread, started lazily by the proxy /
    # emulator via obs.httpd.maybe_start_metrics_http(). Binds loopback
    # only unless metrics_host widens it (the endpoint has no auth).
    metrics_port: int = 0
    metrics_host: str = "127.0.0.1"
    # periodic metrics snapshot-to-file for long soaks: every N seconds the
    # registry's JSON snapshot is written to metrics_snapshot_path.
    # 0 disables (default).
    metrics_snapshot_s: int = 0
    metrics_snapshot_path: str = ""

    # ---- introspection & heat telemetry (obs/profile.py, obs/heat.py) ----
    # per-shard heat accounting: every sharded-store fetch (primary /
    # failover / degraded) charges fetch count, rows, bytes, and latency
    # into per-shard counters (EWMA + histogram), exported as the
    # wukong_shard_heat_* metrics and the /top report. The charge rides the
    # slow host-side fetch path (never per row), so on is the default.
    enable_heat: bool = True
    # per-shard latency / arrival samples kept for the heat CDFs
    heat_window: int = 512
    # latency attribution + regression sentinel: decompose each TRACED
    # query's latency into queue/parse/plan/execute/fetch components,
    # keep a rolling per-template baseline, and auto-dump the trace when a
    # query regresses (component share shift or p95 drift). Needs
    # enable_tracing for samples; off by default like tracing itself.
    enable_attribution: bool = False
    # rolling per-template baseline window (samples kept per template)
    attribution_window: int = 256
    # samples a template needs before the sentinel may flag it
    attribution_min_samples: int = 32
    # regression trip wires: a component's share of total latency moving
    # by more than this many percentage points vs the baseline mean, or a
    # query slower than baseline p95 by more than this percent
    attribution_share_drift_pct: int = 25
    attribution_p95_drift_pct: int = 100
    # after a trip, a template's sentinel re-arms only after this many
    # seconds: one anomaly = one dumped trace, not a log storm when a
    # noisy template keeps wobbling around its own p95
    attribution_cooldown_s: int = 30
    # rows shown per section in the /top report and the `top` console verb
    top_k: int = 8

    # ---- placement observatory (obs/tsdb.py, obs/events.py,
    # obs/placement.py; all mutable) ----
    # metrics time-series ring: sample MetricsRegistry.snapshot() every
    # tsdb_interval_s seconds into a bounded ring tsdb_retention_s deep,
    # answering windowed rate / percentile / range queries (/history, the
    # `history` verb, and the PlacementAdvisor's trend reads). Default ON:
    # one snapshot per interval is far off any hot path.
    enable_tsdb: bool = True
    tsdb_interval_s: int = 5
    tsdb_retention_s: int = 900
    # structured cluster-event journal: breaker trips, failovers, heals,
    # WAL rotations, checkpoint writes, SLO burns, and latency regressions
    # land in a bounded ring (events_ring entries) with shard/tenant/qid
    # correlation keys (/events, the `events` verb, Monitor Events[...]).
    # events_log_path additionally mirrors every event to a JSONL file
    # ("" = in-memory only). Off degrades every emitter to one knob check.
    enable_events: bool = True
    events_ring: int = 512
    events_log_path: str = ""
    # observe-only placement advisor: read the heat plane's PLACEMENT_INPUTS
    # through the tsdb trend window (placement_window_s seconds), score
    # max/mean host load-rate imbalance, and emit a MigrationPlan artifact
    # when it reaches placement_imbalance_x (never touching the store).
    # placement_interval_s > 0 runs the advisory loop in the background;
    # 0 (default) advises on demand only (/plan, the `plan` verb).
    placement_interval_s: int = 0
    placement_window_s: int = 300
    # float: fractional thresholds like 1.5x are legitimate for a
    # max/mean ratio
    placement_imbalance_x: float = 2.0
    # flight-recorder dump-dir retention: keep at most this many
    # trace_*.json files in trace_dump_dir, evicting oldest (0 = unbounded
    # — the pre-observatory behavior; auto-dump storms then grow the dir
    # without limit)
    trace_dump_max: int = 256
    # /healthz readiness semantics: when on, a degraded process (open
    # breakers, degraded/failover shards, dead pool engines) answers 503
    # so a load balancer drains it; liveness stays 200 either way when off
    health_ready_503: bool = False

    # ---- elastic data plane: the live shard-migration actuator
    # (runtime/migration.py; all mutable) ----
    # execute the placement advisor's MigrationPlans (clone -> catch-up ->
    # cutover -> retire). OFF by default: the advisor stays observe-only
    # (the PR 11 posture) and both the `migrate` verb and the executor
    # refuse to move shards. On + placement_interval_s > 0 runs the
    # actuator loop: plans execute continuously against PLACEMENT_INPUTS.
    migration_enable: bool = False
    # cutover posture: on (default) demotes the donor copy to a
    # read-rotation replica on its old host — reads split across
    # donor+recipient, exactly the MigrationPlan's predicted-balance model
    # (replica-read rotation, ROADMAP follow-up j). Off retires the donor
    # copy outright (the recipient serves alone).
    migration_rotate_reads: bool = True

    # ---- tenant-aware SLO plane (obs/slo.py; all mutable) ----
    # per-tenant accounting at the proxy reply point: tenant-labeled reply
    # counters/latency histograms, per-tenant in-flight + arrival-rate
    # EWMAs, and the overload signal bus item 4's admission controller
    # consumes. Default ON: the per-reply cost is a few leaf-lock counter
    # updates. Off degrades every hook to one knob check.
    enable_tenant_accounting: bool = True
    # bounded label cardinality: at most this many distinct tenant label
    # values; later tenants land in the "__overflow__" bucket (a hostile
    # or buggy client must not mint unbounded metric series)
    max_tenants: int = 64
    # config-declared SLO specs: ";"-separated
    # "<tenant>:<percentile>:<latency_ms>:<availability>" entries, e.g.
    # "gold:95:50:0.999;bulk:95:0:0.9" (latency_ms 0 = availability-only).
    # Runtime registration: obs.slo.get_slo().register(SLOSpec(...)).
    slo_specs: str = ""
    # per-tenant reply samples kept for compliance / percentile math
    slo_window: int = 512
    # burn-rate windows (SRE-workbook multi-window): the fast window
    # catches a sudden cliff, the slow window filters blips. Seconds;
    # defaults are the canonical 5m / 1h pair
    slo_fast_window_s: int = 300
    slo_slow_window_s: int = 3600
    # burn-rate thresholds (x the sustainable budget-consumption rate):
    # the sentinel pages only when BOTH windows exceed their threshold
    slo_burn_fast_x: int = 14
    slo_burn_slow_x: int = 6
    # per-tenant sentinel re-arm delay: one burn episode = one counted
    # alert + one dumped trace per window, not a storm
    slo_dump_cooldown_s: int = 60

    # ---- serving-cache observatory (obs/reuse.py; all mutable) ----
    # template popularity ledger + observe-only shadow cache charged at
    # the proxy reply point: per-template windowed arrival rates with
    # tenant attribution, a Zipf-skew estimate, and a version-keyed
    # shadow key ring (key = plan signature + consts + store version,
    # ROADMAP item 7's exact cache key) simulating hit/miss/evict/
    # invalidate WITHOUT storing results. Default ON: the per-reply cost
    # is a few leaf-lock updates; off degrades every hook — including the
    # store-mutation invalidation notes — to one knob check.
    enable_reuse: bool = True
    # per-template arrival samples kept for the windowed rate
    reuse_window: int = 512
    # bounded template-label cardinality: past this many distinct
    # templates, new ones land in the "__overflow__" bucket
    reuse_templates_max: int = 256
    # shadow key ring capacity (the simulated cache's entry budget — the
    # reported hit rate is what a real cache of THIS size would achieve)
    shadow_cache_size: int = 4096
    # sample the shadow probe 1-in-N replies (1 = every reply, the
    # default; raise only if the probe outgrows the leaf-lock budget on
    # the serving micro — the ledger charge always runs)
    reuse_sample_every: int = 1

    # ---- device-cost observatory (wukong_tpu/obs/device.py; all
    # mutable) ----
    # ROADMAP item 8's decision substrate: per-dispatch XLA cost
    # accounting (wall time, live rows vs padded capacity, bytes moved),
    # the compile ledger (cold/warm split, per-site shape variants), and
    # the device-residency ledger (bytes per kind vs the budget).
    # Default ON: the hot serving path carries no device dispatch, so
    # the per-hook cost is one knob check; off degrades every seam to
    # that check.
    enable_device_obs: bool = True
    # device-resident byte ceiling the residency ledger reports against
    # (telemetry only — DeviceStore's own LRU budget keeps enforcing;
    # default mirrors tpu_mem_cache_gb so HBM_BUDGET.md's numbers and
    # the live gauge describe the same ceiling)
    device_budget_mb: int = 4096
    # variant-storm sentinel: a dispatch site minting MORE than this
    # many distinct (template, capacity-class) jit variants inside one
    # sentinel window journals a device.variant_storm ClusterEvent and
    # force-dumps the trace ring — the pad_pow2 capacity-class
    # discipline's regression tripwire
    device_variant_limit: int = 32
    # seconds between variant-storm trips per site (the attribution_
    # cooldown_s posture: one journal + dump per storm, not per dispatch)
    device_storm_cooldown_s: float = 60.0
    # persistent XLA compile-cache directory (utils/compilecache.py);
    # where JAX_COMPILATION_CACHE_DIR is unset; empty = <repo>/.cache/xla
    xla_cache_dir: str = ""
    # XProf/Perfetto capture directory for obs/export.py
    # maybe_device_trace; empty = the WUKONG_XPROF_DIR env form, then no
    # tracing (EXPLAIN ANALYZE's device section points operators here)
    xprof_dir: str = ""

    # ---- materialized-view serving plane (wukong_tpu/serve/; all
    # mutable) ----
    # the REAL version-keyed full-result cache in the proxy reply path
    # (ROADMAP item 7 rung i). OFF by default: the serving path is
    # byte-for-byte unchanged (the migration_enable actuator posture).
    # On, it requires enable_reuse for its admission substrate — with
    # the observatory off the cache admits nothing.
    enable_result_cache: bool = False
    # bound on result bytes held (LRU-evicted past it; one entry may
    # never exceed a quarter of the budget)
    result_cache_mb: int = 64
    # popularity admission: a reply is cached only once its template has
    # this many ledger reads, counting the reply itself (1 = the second
    # serve of a template hits — shadow-cache parity; raise to reserve
    # the byte budget for genuinely recurring templates)
    result_cache_min_reads: int = 1
    # rung ii: promote templates that stay hot across version edges into
    # incrementally-maintained views (semi-naive delta eval per mutation
    # edge re-keys untouched entries, so hits survive writes). Off, the
    # cache keeps the pure rung-i posture: every write kills every key.
    enable_views: bool = False
    # version-edge misses a template must accumulate before promotion
    view_promote_edges: int = 2
    # demote a view touched on more than this percent of its observed
    # edges (>=8 edges seen): maintenance that never saves a hit is
    # rolled back to plain cache entries
    view_demote_touch_pct: int = 60
    # bound on concurrently maintained views
    views_max: int = 64
    # cost-aware admission/eviction (GDSF-lite): entries carry their
    # measured recompute cost, eviction drops the lowest
    # cost x (1 + hits) / bytes score instead of strict LRU, so a
    # cheap-to-recompute giant can no longer evict many expensive small
    # entries. Off restores pure LRU byte accounting.
    result_cache_cost_model: bool = True

    # ---- admission control plane (runtime/admission.py; all mutable) ----
    # the decision half of the tenant SLO plane: per-tenant quotas
    # (token-bucket q/s, in-flight caps, aggregate row budgets),
    # deficit-round-robin weighted-fair scheduling over per-tenant
    # sub-queues, and the three-rung overload degrade ladder (defer ->
    # partial -> CAPACITY_EXCEEDED), consulted at the proxy admission
    # point and reading ONLY ADMISSION_INPUTS signals. OFF by default:
    # the serving path is byte-unchanged until armed (the
    # migration_enable / enable_result_cache actuator posture).
    enable_admission: bool = False
    # ";"-separated per-tenant quota entries
    # "<tenant>:<weight>:<qps>:<inflight>:<rows_per_s>" — weight drives
    # the DRR fair queue and the shed order (lowest weight first); qps 0
    # = no rate quota, inflight 0 = no concurrency cap, rows_per_s 0 =
    # no aggregate row budget. E.g. "gold:8:0:0:0;silver:4:0:0:0;
    # bulk:1:200:8:500000". Tenants not listed get admission_default_*.
    admission_quotas: str = ""
    # weight for tenants without a quota entry (DRR + shed ordering)
    admission_default_weight: int = 1
    # token-bucket burst: a tenant may burst to this many x its q/s
    # quota before the bucket empties
    admission_burst_x: float = 2.0
    # congestion signal: the worst per-lane queue-delay EWMA is compared
    # to this budget; each doubling past it raises the overload level
    # one rung (level 1 defers, 2 marks partial, 3 rejects — applied
    # lowest-weight-first)
    admission_delay_budget_us: int = 20000
    # aggregate in-flight ceiling feeding the same overload level (the
    # congestion signal for direct-execution serving where no pool lane
    # queues exist); 0 derives 4 x the live engine count, or 8 with no
    # pool attached
    admission_max_inflight: int = 0
    # rung-1 defer: how long an admission defers a sheddable query (past
    # the batch window, letting congestion drain); 0 derives
    # 2 x batch_window_us
    admission_defer_ms: int = 0
    # rung-2 degrade: the tightened deadline/row budget stamped on a
    # partial-results admission (mark_partial settles the reply with
    # complete=False through the PR 1 machinery)
    admission_partial_deadline_ms: int = 250
    admission_partial_budget_rows: int = 200000
    # rung-3 rejection: the retry-after hint (seconds) carried by the
    # structured CAPACITY_EXCEEDED reply and the admission.shed event
    admission_retry_after_s: float = 1.0
    # DRR quantum: queue credits granted per round per unit of tenant
    # weight (1 credit = 1 query); weight 8 drains 8 queries per round
    # while weight 1 drains 1
    admission_drr_quantum: int = 1

    # ---- concurrency checking (wukong_tpu/analysis/lockdep.py) ----
    # lockdep-style runtime lock-order checker: locks created through the
    # analysis.lockdep factories become Debug wrappers that record the
    # per-thread acquisition-order graph, report order cycles (potential
    # deadlocks) with both stacks, flag declared-leaf inversions, and
    # export hold/contention histograms. OFF by default and zero-cost off:
    # the factories return plain threading primitives, not wrappers.
    # Consulted at lock CREATION time — flip it before building the
    # objects under test (tests use analysis.lockdep.install()).
    debug_locks: bool = False

    # ---- serving-path batching knobs (runtime/batcher.py; all mutable) ----
    # coalesce live same-template queries into fused dispatches. OFF by
    # default: the serving path is byte-for-byte unchanged unless enabled.
    enable_batching: bool = False
    # how long the first query of a group waits for company before the
    # group flushes anyway (the Orca-style iteration window)
    batch_window_us: int = 2000
    # a group reaching this many members flushes immediately
    batch_max_size: int = 64
    # a query whose deadline has less than deadline_bypass_factor x
    # batch_window_us remaining skips the batcher entirely
    batch_deadline_bypass_factor: int = 4
    # bounded-LRU sizes for the proxy's parse cache (query text -> parsed
    # query) and plan cache (template signature + store version -> plan)
    parse_cache_size: int = 512
    plan_cache_size: int = 512

    # ---- heavy-lane serving knobs (runtime/batcher.py heavy path; all
    # mutable). Index-origin (wide-table) queries are the serving path's
    # second fusable class: identical heavy templates coalesce into ONE
    # sliced device dispatch (execute_batch_index) whose per-slice counts
    # settle every waiter, and oversized dispatches split across pool
    # engines by slice range with a gather barrier. ----
    # admit index-origin templates into the batcher's heavy lane (only
    # meaningful with enable_batching on; heavy fusion needs blind mode
    # and a device engine)
    heavy_lane: bool = True
    # ceiling on the per-dispatch slice count suggest_index_batch may pick
    # (the emulator's old ad-hoc min(.., 64) cap, now config)
    heavy_batch_max: int = 64
    # index lists at least this long split their fused dispatch across
    # pool engines by slice range (gather barrier reassembles counts).
    # Per-dispatch fixed cost is ~10ms on this container, so small scans
    # LOSE total CPU by splitting — only genuinely big index lists
    # (at-scale datasets) should fan out
    heavy_split_threshold: int = 100000
    # maximum split parts per fused heavy dispatch
    heavy_split_max: int = 4
    # weighted heavy lane: at most this percent of pool engines may
    # execute heavy dispatches concurrently (min 1), so fused heavy work
    # can never starve interactive light traffic
    heavy_lane_pct: int = 50
    # plan-time lane routing (planner estimate_chain peak): a template
    # whose estimated peak intermediate rows reach this threshold is
    # classified heavy even without an index-origin start
    heavy_rows_threshold: int = 100000

    # ---- tensor-join (WCOJ) execution knobs (wukong_tpu/join/; all
    # mutable). The planner picks an execution strategy per query:
    # the expand-per-step walk, or the worst-case-optimal level-at-a-time
    # join for cyclic/analytic shapes whose walk intermediates blow up. ----
    # strategy selection: auto (planner chooses from the estimated
    # intermediate-vs-fragment cardinality ratio; acyclic queries always
    # walk), walk (force the walk), wcoj (force the tensor join on every
    # supported shape)
    join_strategy: str = "auto"
    # auto routes wcoj when the walk's estimated peak intermediate rows
    # reach this multiple of the estimated final fragment size (the
    # wedge-blowup signature); below it the walk's simpler kernels win
    wcoj_ratio: int = 4
    # auto additionally requires the estimated peak to reach this many
    # rows: a blowup measured in thousands is cheaper to walk through
    # than to pay the per-level intersection overhead for
    wcoj_min_rows: int = 8192
    # bounded cache of materialized sorted edge tables / index lists
    # (entries, keyed per store version like the plan cache)
    join_table_cache: int = 64
    # WCOJ level execution route: host (NumPy kernels), device (force the
    # XLA path on every level), auto (route device when the estimated
    # per-level candidate volume amortizes the dispatch cost — see
    # join_device_min_candidates). Any device-path failure degrades the
    # level to the host kernels, mirroring the wcoj->walk posture.
    join_device: str = "auto"
    # dispatch-amortization threshold: under `auto`, the device route is
    # chosen only when the estimated candidate volume reaches this many
    # rows, and a level probes on-device only past it (a padded XLA
    # dispatch costs ~ms; small levels are cheaper on the host kernels).
    # The measured-candidate feedback demotes templates that routed
    # device on an over-predicted estimate back to host.
    join_device_min_candidates: int = 65536
    # whole-plan compiled template execution route (engine/
    # template_compile.py): host (the walk engine), device (force the
    # fused XLA program on every eligible template), auto (the rule
    # under template_min_rows). Any compile or mid-flight dispatch
    # failure degrades the query to the walk byte-identically and latches
    # a per-template demotion.
    template_device: str = "auto"
    # the row count the `auto` rule turns on. Programs win at both ends
    # and the walk keeps the middle. Few padded rows (every capacity
    # class of the plan's program under this many, and the walk the
    # device engine): the calls are the cost, and one program beats the
    # walk's jitted call a step. Many live rows (the planner's estimated
    # peak at or over this many): the device is the cost. Large padded
    # classes with a small reply (a served program with a class at or
    # over this many whose reply holds fewer live rows is demoted): the
    # walk, which compacts between steps. Where the walk is NumPy on the
    # host it makes no calls, and only the estimate routes to a program.
    template_min_rows: int = 4096
    # capacity-overflow retries: a compiled run whose padded table
    # overflows regrows its capacity classes (the class of the measured
    # total, at least twice the one that overflowed) and re-dispatches at
    # most this many times before degrading to the host walk
    template_capacity_retries: int = 3
    # byte budget for what cached compiled-template programs keep staged
    # on the device (their start lists; a run's result buffer is not
    # counted); cold programs past it are LRU-evicted (charged on the
    # residency ledger, kind "template")
    template_budget_mb: int = 256
    # distributed generic join: max slice-range parts a cyclic query over
    # a sharded store fans out to on the heavy lane (hash-partitioning
    # the first eliminated variable); bounded by the shard count and the
    # pool's live engines. 1 disables the fan-out (single-engine wcoj
    # over the federated view).
    join_dist_parts: int = 4

    # ---- hybrid graph+vector knobs (wukong_tpu/vector/; runtime-mutable) ----
    # master switch for the vector subsystem: off keeps the serving path
    # byte-identical (one knob check per knn-free query — the
    # enable_result_cache / enable_admission actuator posture). A query
    # carrying a knn() clause while this is off is refused, never
    # silently degraded.
    enable_vectors: bool = False
    # fixed embedding width of every attached vector store; upserts with
    # any other width are refused (the [n_slots, dim] block layout is
    # shape-stable so the jitted scan compiles one variant per store)
    vector_dim: int = 64
    # k-NN similarity behind the one kernel seam: cosine | dot | l2
    # (l2 ranks by NEGATIVE squared distance so "higher score = nearer"
    # holds across all three metrics)
    knn_metric: str = "cosine"
    # k-NN scan route: host (NumPy brute force), device (force the jitted
    # XLA batched-matmul scan), auto (device when the candidate volume
    # amortizes the dispatch — knn_split_threshold — with measured
    # demotion back to host on device failure, the join_device posture)
    knn_device: str = "auto"
    # wide-scan threshold (live vectors): at or past it a full-store scan
    # classifies down the heavy lane and splits into slice ranges across
    # the engine pool (join/dist.py gather-barrier shape); under
    # knn_device=auto it is also the device-dispatch amortization floor
    knn_split_threshold: int = 65536

    # ---- TPU-engine knobs (new; no reference analogue) ----
    table_capacity_min: int = 1024  # smallest binding-table capacity class
    # largest capacity class: 32M rows x 8 cols x int32 = 1 GiB, within one
    # v5e chip's HBM alongside staged segments (LUBM-2560 heavy queries peak
    # near 10-30M intermediate rows)
    table_capacity_max: int = 1 << 25
    exchange_capacity: int = 1 << 16  # per-destination all-to-all row budget
    device_batch: int = 1024  # queries compiled together (emulator batch dim)

    # ---- derived (recomputed by finalize; config.hpp:220-235) ----
    num_threads: int = field(default=0, init=False)

    _IMMUTABLE = {
        "num_workers", "num_proxies", "num_engines", "input_folder",
        "memstore_size_gb", "est_bdr_threshold", "enable_tpu", "tpu_mem_cache_gb",
        "enable_dynamic_store", "enable_versatile", "replication_factor",
    }

    def finalize(self) -> None:
        self.num_threads = self.num_proxies + self.num_engines
        # mt_threshold never exceeds engine count (config.hpp:231)
        self.mt_threshold = max(1, min(self.mt_threshold, self.num_engines))

    def set(self, key: str, value: str, runtime: bool = False) -> None:
        """Set one key from its string form. runtime=True rejects immutable keys."""
        self._apply(key, value, runtime)
        self.finalize()

    def _apply(self, key: str, value: str, runtime: bool) -> None:
        key = key.removeprefix("global_")
        valid = {f.name for f in fields(self) if f.init}
        if key not in valid:
            raise KeyError(f"unknown config item: {key}")
        if runtime and key in self._IMMUTABLE:
            raise ValueError(f"config item '{key}' is immutable at runtime")
        cur = getattr(self, key)
        if isinstance(cur, bool):
            setattr(self, key, value.strip().lower() in ("1", "true", "yes", "on"))
        elif isinstance(cur, int):
            setattr(self, key, int(value))
        elif isinstance(cur, float):
            setattr(self, key, float(value))
        else:
            setattr(self, key, value.strip())

    def load_str(self, text: str, runtime: bool = False) -> None:
        """Parse 'key value' lines (comments with #) — config.hpp:152-181.

        All items are parsed and validated before any is applied (the reference
        builds a full item map first, config.hpp str2items), so a bad line
        leaves the config untouched; unknown keys warn and are skipped
        (config.hpp warns rather than aborting). Derived invariants are
        recomputed once at the end, keeping clamps order-independent.
        """
        from wukong_tpu.utils.logger import log_warn

        items: list[tuple[str, str]] = []
        for line in text.splitlines():
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            parts = line.split(None, 1)
            if len(parts) != 2:
                raise ValueError(f"malformed config line: {line!r}")
            items.append((parts[0], parts[1]))
        valid = {f.name for f in fields(self) if f.init}
        known = [(k, v) for k, v in items if k.removeprefix("global_") in valid]
        for k, v in items:
            if k.removeprefix("global_") not in valid:
                log_warn(f"unknown config item ignored: {k}")
        # validate before applying (immutability + int parse)
        for k, v in known:
            key = k.removeprefix("global_")
            if runtime and key in self._IMMUTABLE:
                raise ValueError(f"config item '{key}' is immutable at runtime")
            if isinstance(getattr(self, key), bool):
                pass
            elif isinstance(getattr(self, key), int):
                int(v)  # raises ValueError on junk before anything is applied
        for k, v in known:
            self._apply(k, v, runtime)
        self.finalize()

    def load_file(self, path: str, runtime: bool = False) -> None:
        with open(path) as f:
            self.load_str(f.read(), runtime=runtime)

    def dump(self) -> str:
        out = []
        for f in fields(self):
            if f.init:
                out.append(f"global_{f.name}\t{getattr(self, f.name)}")
        return "\n".join(out)


# process-wide singleton, mirroring `Global::*` statics (global.hpp:29-74)
Global = GlobalConfig()
Global.finalize()


def load_config(path: str, num_workers: int | None = None) -> GlobalConfig:
    """Boot-time load (config.hpp:203-218): file + worker count from the launcher."""
    Global.load_file(path)
    if num_workers is not None:
        Global.num_workers = num_workers
    Global.finalize()
    return Global


def reload_config(text: str) -> GlobalConfig:
    """Runtime reload of mutable settings (config.hpp:183-198)."""
    Global.load_str(text, runtime=True)
    return Global
