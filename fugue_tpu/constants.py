"""Global configuration registry and well-known config keys.

Layered config model (parity with reference fugue/constants.py:35-51):
global conf (this module) <- engine conf at construction <- per-run overrides.

Every ``FUGUE_CONF_*`` key is DECLARED in :data:`_CONF_REGISTRY` below with
its value type, default, and a one-line description; ``_DEFAULT_CONF`` (the
seed of the global conf every engine/workflow inherits) is derived from that
table, so the registry is the single source of truth shared by the engine
conf getters and the static analyzer's conf pass
(:mod:`fugue_tpu.analysis`), which flags unknown ``fugue.*`` keys with a
did-you-mean suggestion and values not convertible to the declared type.
"""

from typing import Any, Dict, NamedTuple

from fugue_tpu.utils.params import ParamDict, _convert

KEYWORD_ROWCOUNT = "ROWCOUNT"
KEYWORD_PARALLELISM = "CONCURRENCY"

FUGUE_CONF_ANALYSIS = "fugue.analysis"
FUGUE_CONF_WORKFLOW_CONCURRENCY = "fugue.workflow.concurrency"
FUGUE_CONF_WORKFLOW_CHECKPOINT_PATH = "fugue.workflow.checkpoint.path"
FUGUE_CONF_WORKFLOW_RETRY_MAX_ATTEMPTS = "fugue.workflow.retry.max_attempts"
FUGUE_CONF_WORKFLOW_RETRY_BACKOFF = "fugue.workflow.retry.backoff"
FUGUE_CONF_WORKFLOW_RETRY_JITTER = "fugue.workflow.retry.jitter"
FUGUE_CONF_WORKFLOW_TIMEOUT = "fugue.workflow.timeout"
FUGUE_CONF_WORKFLOW_RESUME = "fugue.workflow.resume"
FUGUE_CONF_WORKFLOW_EXCEPTION_HIDE = "fugue.workflow.exception.hide"
FUGUE_CONF_WORKFLOW_EXCEPTION_INJECT = "fugue.workflow.exception.inject"
FUGUE_CONF_WORKFLOW_EXCEPTION_OPTIMIZE = "fugue.workflow.exception.optimize"
FUGUE_CONF_SQL_IGNORE_CASE = "fugue.sql.compile.ignore_case"
FUGUE_CONF_SQL_DIALECT = "fugue.sql.compile.dialect"
FUGUE_CONF_RPC_SERVER = "fugue.rpc.server"
FUGUE_CONF_JAX_PARTITIONS = "fugue.jax.default.partitions"
FUGUE_CONF_JAX_COMPILE = "fugue.jax.compile"
FUGUE_CONF_JAX_ROW_BUCKET = "fugue.jax.row_bucket"
FUGUE_CONF_JAX_DEVICE_ZIP = "fugue.jax.device_zip"
FUGUE_CONF_JAX_PLACEMENT = "fugue.jax.placement"
FUGUE_CONF_JAX_MIN_DEVICE_BYTES = "fugue.jax.placement.min_device_bytes"
FUGUE_CONF_JAX_COMPILE_CACHE = "fugue.jax.compile.cache"
FUGUE_CONF_JAX_IO_BATCH_ROWS = "fugue.jax.io.batch_rows"
FUGUE_CONF_JAX_IO_PIPELINE = "fugue.jax.io.pipeline"
FUGUE_CONF_JAX_GROUPBY_MATMUL = "fugue.jax.groupby.matmul"
FUGUE_CONF_JAX_GROUPBY_STRATEGY = "fugue.jax.groupby.strategy"
FUGUE_CONF_JAX_GROUPBY_AUTOTUNE = "fugue.jax.groupby.autotune"
FUGUE_CONF_JAX_SHUFFLE = "fugue.jax.shuffle"
FUGUE_CONF_JAX_SHUFFLE_OVERLAP = "fugue.jax.shuffle.overlap"
FUGUE_CONF_JAX_DEVICES = "fugue.jax.devices"
FUGUE_CONF_JAX_MEMORY_BUDGET_BYTES = "fugue.jax.memory.budget_bytes"
FUGUE_CONF_JAX_MEMORY_BUDGET_FRACTION = "fugue.jax.memory.budget_fraction"
FUGUE_CONF_JAX_MEMORY_HIGH_WATERMARK = "fugue.jax.memory.high_watermark"
FUGUE_CONF_JAX_MEMORY_LOW_WATERMARK = "fugue.jax.memory.low_watermark"
FUGUE_CONF_JAX_RECOVERY_ENABLED = "fugue.jax.recovery.enabled"
FUGUE_CONF_JAX_RECOVERY_MAX_LOSSES = "fugue.jax.recovery.max_losses"
FUGUE_CONF_RPC_HTTP_RETRIES = "fugue.rpc.http_server.retries"
FUGUE_CONF_RPC_HTTP_MAX_BODY = "fugue.rpc.http_server.max_body_bytes"
FUGUE_CONF_RPC_HTTP_READ_TIMEOUT = "fugue.rpc.http_server.read_timeout"
FUGUE_CONF_SERVE_HOST = "fugue.serve.host"
FUGUE_CONF_SERVE_PORT = "fugue.serve.port"
FUGUE_CONF_SERVE_MAX_CONCURRENT = "fugue.serve.max_concurrent"
FUGUE_CONF_SERVE_SESSION_TTL = "fugue.serve.session_ttl"
FUGUE_CONF_SERVE_SYNC_WAIT = "fugue.serve.sync_wait"
FUGUE_CONF_SERVE_TENANT_BUDGET_FRACTION = "fugue.serve.tenant_budget_fraction"
FUGUE_CONF_SERVE_STATE_PATH = "fugue.serve.state_path"
FUGUE_CONF_SERVE_DRAIN_TIMEOUT = "fugue.serve.drain_timeout"
FUGUE_CONF_SERVE_MAX_QUEUE = "fugue.serve.max_queue"
FUGUE_CONF_SERVE_SESSION_MAX_JOBS = "fugue.serve.session_max_jobs"
FUGUE_CONF_SERVE_MEMORY_REJECT = "fugue.serve.memory_reject_fraction"
FUGUE_CONF_SERVE_SYNC_DEGRADE_DEPTH = "fugue.serve.sync_degrade_depth"
FUGUE_CONF_SERVE_BREAKER_THRESHOLD = "fugue.serve.breaker.threshold"
FUGUE_CONF_SERVE_BREAKER_COOLDOWN = "fugue.serve.breaker.cooldown"
FUGUE_CONF_SERVE_HEARTBEAT_TIMEOUT = "fugue.serve.heartbeat_timeout"
FUGUE_CONF_SERVE_JOB_TTL = "fugue.serve.job_ttl"
FUGUE_CONF_SERVE_CLIENT_RETRIES = "fugue.serve.client.retries"
FUGUE_CONF_SERVE_PREWARM = "fugue.serve.prewarm"
FUGUE_CONF_SERVE_FLEET_REPLICAS = "fugue.serve.fleet.replicas"
FUGUE_CONF_SERVE_FLEET_HOST = "fugue.serve.fleet.host"
FUGUE_CONF_SERVE_FLEET_PORT = "fugue.serve.fleet.port"
FUGUE_CONF_SERVE_FLEET_HEALTH_INTERVAL = "fugue.serve.fleet.health_interval"
FUGUE_CONF_SERVE_FLEET_DEATH_THRESHOLD = "fugue.serve.fleet.death_threshold"
FUGUE_CONF_SERVE_FLEET_RESULT_CACHE_DIR = "fugue.serve.fleet.result_cache_dir"
FUGUE_CONF_SERVE_FLEET_DEVICE_SLICES = "fugue.serve.fleet.device_slices"
FUGUE_CONF_SERVE_SCHEDULER = "fugue.serve.scheduler"
FUGUE_CONF_SERVE_ADMISSION_MEMORY_FRACTION = (
    "fugue.serve.admission.memory_fraction"
)
FUGUE_CONF_SERVE_ADMISSION_MAX_WAIT = "fugue.serve.admission.max_predicted_wait"
FUGUE_CONF_SERVE_ADMISSION_DEFAULT_MS = "fugue.serve.admission.default_cost_ms"
FUGUE_CONF_SERVE_ADMISSION_DEFAULT_BYTES = (
    "fugue.serve.admission.default_cost_bytes"
)
FUGUE_CONF_SERVE_AUTOSCALE_MAX_REPLICAS = "fugue.serve.autoscale.max_replicas"
FUGUE_CONF_SERVE_AUTOSCALE_MIN_REPLICAS = "fugue.serve.autoscale.min_replicas"
FUGUE_CONF_SERVE_AUTOSCALE_INTERVAL = "fugue.serve.autoscale.interval"
FUGUE_CONF_SERVE_AUTOSCALE_UP_QUEUE = "fugue.serve.autoscale.scale_up_queue"
FUGUE_CONF_SERVE_AUTOSCALE_UP_P99_MS = "fugue.serve.autoscale.scale_up_p99_ms"
FUGUE_CONF_SERVE_AUTOSCALE_SUSTAIN_TICKS = "fugue.serve.autoscale.sustain_ticks"
FUGUE_CONF_SERVE_AUTOSCALE_IDLE_TICKS = "fugue.serve.autoscale.idle_ticks"
FUGUE_CONF_SERVE_AUTOSCALE_COOLDOWN = "fugue.serve.autoscale.cooldown"
FUGUE_CONF_OPTIMIZE = "fugue.optimize"
FUGUE_CONF_OPTIMIZE_CSE = "fugue.optimize.cse"
FUGUE_CONF_OPTIMIZE_FILTER = "fugue.optimize.filter_pushdown"
FUGUE_CONF_OPTIMIZE_FUSION = "fugue.optimize.fusion"
FUGUE_CONF_OPTIMIZE_PROJECTION = "fugue.optimize.projection_pushdown"
FUGUE_CONF_OPTIMIZE_RESULT_CACHE = "fugue.optimize.result_cache"
FUGUE_CONF_OPTIMIZE_CACHE_MAX_ENTRIES = "fugue.optimize.cache.max_entries"
FUGUE_CONF_OPTIMIZE_CACHE_MAX_PROGRAMS = "fugue.optimize.cache.max_programs"
FUGUE_CONF_OPTIMIZE_CACHE_MAX_RESULT_BYTES = (
    "fugue.optimize.cache.max_result_bytes"
)
FUGUE_CONF_OPTIMIZE_CACHE_DIR = "fugue.optimize.cache.dir"
FUGUE_CONF_SERVE_RESULT_CACHE = "fugue.serve.result_cache"
FUGUE_CONF_DEBUG_LOCK_SANITIZER = "fugue.debug.lock_sanitizer"
FUGUE_CONF_DEBUG_RETRACE_SENTINEL = "fugue.debug.retrace_sentinel"
FUGUE_CONF_DEBUG_RETRACE_SENTINEL_MAX_TRACES = (
    "fugue.debug.retrace_sentinel.max_traces"
)
FUGUE_CONF_DEBUG_RETRACE_SENTINEL_RAISE = "fugue.debug.retrace_sentinel.raise"
FUGUE_CONF_OBS_ENABLED = "fugue.obs.enabled"
FUGUE_CONF_OBS_TRACE_PATH = "fugue.obs.trace_path"
FUGUE_CONF_OBS_SLOW_QUERY_MS = "fugue.obs.slow_query_ms"
FUGUE_CONF_OBS_SAMPLE_RATE = "fugue.obs.sample_rate"
FUGUE_CONF_OBS_PROFILE = "fugue.obs.profile"
FUGUE_CONF_STATS_PATH = "fugue.stats.path"
FUGUE_CONF_STATS_HISTORY = "fugue.stats.history"
FUGUE_CONF_STREAM_SOURCE = "fugue.stream.source"
FUGUE_CONF_STREAM_PATTERN = "fugue.stream.pattern"
FUGUE_CONF_STREAM_INTERVAL = "fugue.stream.interval"
FUGUE_CONF_STREAM_WATERMARK_DELAY = "fugue.stream.watermark.delay"
FUGUE_CONF_STREAM_MAX_FILES = "fugue.stream.max_files_per_batch"
FUGUE_CONF_STREAM_BATCH_ROWS = "fugue.stream.batch_rows"
FUGUE_CONF_LAKE_COMMIT_RETRIES = "fugue.lake.commit.retries"
FUGUE_CONF_LAKE_COMMIT_BACKOFF = "fugue.lake.commit.backoff"
FUGUE_CONF_LAKE_COMPACT_TARGET_ROWS = "fugue.lake.compact.target_rows"
FUGUE_CONF_LAKE_SERVE_PATH = "fugue.lake.serve.path"
FUGUE_CONF_LAKE_VERIFY = "fugue.lake.verify"

FUGUE_COMPILE_TIME_CONFIGS = {
    FUGUE_CONF_WORKFLOW_EXCEPTION_HIDE,
    FUGUE_CONF_WORKFLOW_EXCEPTION_INJECT,
    FUGUE_CONF_WORKFLOW_EXCEPTION_OPTIMIZE,
    FUGUE_CONF_SQL_IGNORE_CASE,
    FUGUE_CONF_SQL_DIALECT,
}

class ConfKeyInfo(NamedTuple):
    """One declared conf key: its value type (``object`` = unchecked),
    default, and description. ``in_defaults=False`` keys are declared (the
    analyzer knows them) but deliberately NOT seeded into the global conf
    (e.g. legacy/no-op knobs)."""

    key: str
    type: type
    default: Any
    description: str
    in_defaults: bool = True


_CONF_REGISTRY: Dict[str, ConfKeyInfo] = {}


def register_conf_key(
    key: str,
    type_: type,
    default: Any,
    description: str,
    in_defaults: bool = True,
) -> None:
    """Declare a conf key (type + default + description). Backends and
    plugins may call this for their own ``fugue.*`` keys so the static
    analyzer recognizes them; keys registered after import time extend the
    live registry but not the already-built global defaults."""
    _CONF_REGISTRY[key] = ConfKeyInfo(key, type_, default, description, in_defaults)


def declared_conf_keys() -> Dict[str, ConfKeyInfo]:
    """Snapshot of every declared conf key (key -> ConfKeyInfo). Shared by
    the engine conf getters (via :func:`conf_default`) and the analyzer's
    conf pass."""
    return dict(_CONF_REGISTRY)


def conf_default(key: str) -> Any:
    """The registered default of a declared conf key."""
    return _CONF_REGISTRY[key].default


def typed_conf_get(conf: Any, key: str) -> Any:
    """Read a declared key from a conf mapping: missing keys return the
    registered default, present values coerce to the key's DECLARED type
    (the same ``_convert`` semantics the analyzer's FWF202 rule checks;
    ``object``-typed keys pass through untouched)."""
    info = _CONF_REGISTRY[key]
    if key not in conf:
        return info.default
    value = conf[key]
    if info.type is object:
        return value
    return _convert(value, info.type)


def _declare_defaults() -> None:
    r = register_conf_key
    r(
        FUGUE_CONF_ANALYSIS,
        str,
        "warn",
        "pre-execution static analysis of the workflow DAG: 'off' skips it, "
        "'warn' (default) logs diagnostics and proceeds, 'error' raises "
        "before any task executes when error-level diagnostics exist",
    )
    r(FUGUE_CONF_WORKFLOW_CONCURRENCY, int, 1, "parallel task slots of the DAG runner")
    # fault tolerance: attempts = 1 means no retry; backoff is the base
    # exponential delay in seconds (delay = backoff * 2**(attempt-1)),
    # jitter a multiplicative fraction added on top. Only TRANSIENT error
    # classes retry (fs/IO, RPC transport, jax RESOURCE_EXHAUSTED) — see
    # fugue_tpu/workflow/fault.py:classify_error. timeout is the per-task
    # wall clock in seconds (0 = unlimited), enforced by the parallel
    # runner. resume=True keeps a run manifest of completed task uuids so
    # re-running an identical DAG after a crash restarts at the frontier.
    r(FUGUE_CONF_WORKFLOW_RETRY_MAX_ATTEMPTS, int, 1, "task attempts (1 = no retry)")
    r(FUGUE_CONF_WORKFLOW_RETRY_BACKOFF, float, 0.1, "base exponential retry delay (s)")
    r(FUGUE_CONF_WORKFLOW_RETRY_JITTER, float, 0.1, "multiplicative retry jitter fraction")
    r(FUGUE_CONF_WORKFLOW_TIMEOUT, float, 0.0, "per-task wall clock (s, 0 = unlimited)")
    r(FUGUE_CONF_WORKFLOW_RESUME, bool, False, "manifest-backed resume of crashed runs")
    r(
        FUGUE_CONF_WORKFLOW_CHECKPOINT_PATH,
        str,
        "",
        "durable dir/URI for strong checkpoints, yields and run manifests",
    )
    r(FUGUE_CONF_WORKFLOW_EXCEPTION_HIDE, str, "fugue_tpu.", "module prefix hidden from tracebacks")
    r(FUGUE_CONF_WORKFLOW_EXCEPTION_INJECT, int, 3, "user stack frames attached to task errors")
    r(FUGUE_CONF_WORKFLOW_EXCEPTION_OPTIMIZE, bool, True, "prune framework frames from tracebacks")
    r(FUGUE_CONF_SQL_IGNORE_CASE, bool, False, "case-insensitive FugueSQL keywords")
    r(FUGUE_CONF_SQL_DIALECT, str, "spark", "SQL dialect of raw SELECT statements")
    r(FUGUE_CONF_RPC_SERVER, str, "native", "driver<->worker RPC server ('native' or 'http')")
    r(
        FUGUE_CONF_RPC_HTTP_RETRIES,
        int,
        2,
        # bounded exponential-backoff retries for the HTTP RPC client on
        # transient transport failures (connection refused/reset, HTTP 503);
        # non-transient HTTP errors always fail fast
        "HTTP RPC client retries on transient transport failures",
    )
    r(
        FUGUE_CONF_JAX_PARTITIONS,
        int,
        0,
        "logical split count for host-fallback maps (0 = mesh size)",
    )
    # legacy/no-op: compilation is always on; declared so old confs lint clean
    r(FUGUE_CONF_JAX_COMPILE, bool, True, "legacy no-op (compilation is always on)", in_defaults=False)
    r(
        FUGUE_CONF_JAX_ROW_BUCKET,
        int,
        0,
        "round row counts up to multiples of this before compile so nearby "
        "shapes share programs (0 = exact shapes; every distinct row count "
        "compiles its own program)",
    )
    r(FUGUE_CONF_JAX_DEVICE_ZIP, bool, True, "device-side zip of co-partitioned frames")
    # Two-tier placement (see JaxExecutionEngine): frames below the byte
    # threshold ingest onto the host (CPU-XLA) mesh; at/above it they go to
    # the accelerator mesh. The default is tuned for network-attached
    # accelerators where per-query host<->device transfer costs seconds per
    # GB; on PCIe-local TPU hosts set a lower threshold or placement=device.
    r(FUGUE_CONF_JAX_PLACEMENT, str, "auto", "ingest tier: auto | device | host")
    r(
        FUGUE_CONF_JAX_MIN_DEVICE_BYTES,
        int,
        256 * 1024 * 1024,
        "auto-placement threshold: smaller frames stay on the host tier",
    )
    # DEPRECATED alias of fugue.optimize.cache.dir (the persistent
    # executable cache that replaced jax's own compilation cache here).
    # Precedence: fugue.optimize.cache.dir wins when both are set; a
    # value arriving only through this key (or the FUGUE_JAX_COMPILE_CACHE
    # env var) still enables the SAME disk tier, with a deprecation note
    # logged — two divergent caches never run side by side.
    r(
        FUGUE_CONF_JAX_COMPILE_CACHE,
        str,
        "",
        "DEPRECATED alias of fugue.optimize.cache.dir (persistent "
        "executable cache dir); the new key wins when both are set",
    )
    # streamed parquet ingest/save: 0 = eager (whole-table). > 0 pipelines
    # arrow record-batch decode with per-shard device_put staging on load
    # (each mesh shard ships as soon as its rows are decoded, while the
    # next batches decode) and bounds parquet row groups on save. The
    # ingest stays LAZY: host-only chains never pay a device round trip.
    r(FUGUE_CONF_JAX_IO_BATCH_ROWS, int, 0, "streamed parquet ingest batch rows (0 = eager)")
    # end-to-end IO pipelining over the streamed paths (requires
    # batch_rows > 0): on load, the first batches kick a background warm
    # of the persistent-executable cache so the first dispatch after
    # assembly is execute-only; on save, row-group encode/write of chunk
    # k overlaps the device->host fetch of chunk k+1. Results and row
    # order are identical to the unpipelined stream (parity-tested).
    r(
        FUGUE_CONF_JAX_IO_PIPELINE,
        bool,
        True,
        "overlap streamed-IO decode/staging with executable warm (load) "
        "and row-group writes with result fetch (save)",
    )
    # group-by reduction algorithm (legacy knob, kept for back-compat):
    # "always"/"never" pin the strategy below to matmul/scatter; "auto"
    # defers to fugue.jax.groupby.strategy.
    r(FUGUE_CONF_JAX_GROUPBY_MATMUL, str, "auto", "legacy matmul pin: auto | always | never")
    # segment-reduction strategy: "auto" consults the measured crossover
    # table in jax_backend/segtune.py (scatter on CPU meshes; on
    # accelerators masked VPU sums for f64 payloads over few bins, one-hot
    # matmul below the segment cap, sorted scatter above it), sharpened
    # by a one-shot on-device autotune; or pin one of "matmul" |
    # "matmul_bf16" | "scatter" | "sort" | "masked". matmul_bf16 trades
    # ~8 mantissa bits for speed and is PIN-ONLY — auto never picks it.
    r(
        FUGUE_CONF_JAX_GROUPBY_STRATEGY,
        str,
        "auto",
        "segment-reduction kernel: "
        "auto | matmul | matmul_bf16 | scatter | sort | masked",
    )
    # autotune policy: "auto" probes on accelerator meshes for large
    # frames only; True/False force it on/off. Mixed-type by design.
    r(FUGUE_CONF_JAX_GROUPBY_AUTOTUNE, object, "auto", "one-shot strategy autotune: auto | bool")
    # all-to-all shuffle repartition (jax_backend/shuffle.py): co-locate
    # matching keys per device shard before segment reductions (group-by,
    # join match counts). "auto" shuffles only on multi-device meshes for
    # frames large enough to amortize the padded receive; "on"/"off" pin
    # it. Single-device meshes never shuffle regardless.
    r(FUGUE_CONF_JAX_SHUFFLE, str, "auto", "key-shuffle repartition: auto | on | off")
    # collective/compute overlap: double-buffer the next key-range's
    # all-to-all behind the current range's local reduction. "auto"
    # enables it on accelerator meshes only (CPU collectives are
    # synchronous, so the split is pure overhead there).
    r(FUGUE_CONF_JAX_SHUFFLE_OVERLAP, str, "auto", "shuffle/compute overlap: auto | on | off")
    # device slice for the engine's mesh: a comma-separated list of
    # indices into jax.devices() (e.g. "0,1"). Empty = all devices. How
    # a serve fleet gives each replica its own slice of the pod.
    r(FUGUE_CONF_JAX_DEVICES, str, "", "engine device slice: comma-separated jax.devices() indices")
    # device-memory governance (jax_backend/memory.py): budget_bytes > 0
    # (or budget_fraction > 0 of the detected per-device memory) turns on
    # the HBM byte ledger + admission controller. An ingest/persist that
    # would push the device tier past high_watermark * budget first
    # spills LRU persisted frames to the host tier down to low_watermark;
    # a frame whose estimated footprint alone exceeds the budget is
    # placed on the host tier directly. 0/0.0 = ungoverned (default).
    r(FUGUE_CONF_JAX_MEMORY_BUDGET_BYTES, int, 0, "device-memory budget bytes (0 = ungoverned)")
    r(
        FUGUE_CONF_JAX_MEMORY_BUDGET_FRACTION,
        float,
        0.0,
        "budget as a fraction of detected per-device memory",
    )
    r(FUGUE_CONF_JAX_MEMORY_HIGH_WATERMARK, float, 0.9, "admission spill trigger fraction")
    r(FUGUE_CONF_JAX_MEMORY_LOW_WATERMARK, float, 0.75, "spill-down target fraction")
    # device-fault resilience (engine.recover_from_device_loss): on a
    # DEVICE_LOST-classified XLA error the engine rebuilds its mesh from
    # the surviving devices, evacuates/re-reads live frames, and retries
    # the task under the normal backoff budget. Frames without
    # recoverable lineage fail their owning query with DeviceLostError —
    # never the process. Needs >1 device to have survivors (FWF509 warns
    # when fugue.jax.devices pins a single device). Read with a local
    # default-on fallback by the engine rather than seeded into every
    # conf (in_defaults=False), so FWF509 only fires on EXPLICIT keys.
    r(
        FUGUE_CONF_JAX_RECOVERY_ENABLED,
        bool,
        True,
        "degraded-mesh rebuild + block evacuation on device loss",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_JAX_RECOVERY_MAX_LOSSES,
        int,
        0,
        "cumulative device losses an engine absorbs before failing fast "
        "(0 = unlimited; each loss shrinks the mesh by the dead devices)",
        in_defaults=False,
    )
    # consumed with local fallbacks by their owning modules (multi-process
    # init in jax_backend/distributed.py, HTTP RPC in rpc/http.py) rather
    # than through the global defaults table — declared here so the
    # analyzer's conf pass recognizes them, NOT seeded (in_defaults=False)
    r(
        "fugue.jax.dist.coordinator",
        str,
        "",
        "host:port of process 0 for multi-process jax init",
        in_defaults=False,
    )
    r(
        "fugue.jax.dist.num_processes",
        int,
        1,
        "total process count of the multi-process mesh",
        in_defaults=False,
    )
    r(
        "fugue.jax.dist.process_id",
        int,
        0,
        "this process's index in the multi-process mesh",
        in_defaults=False,
    )
    r(
        "fugue.rpc.http_server.host",
        str,
        "127.0.0.1",
        "bind/connect host of the HTTP RPC server",
        in_defaults=False,
    )
    r(
        "fugue.rpc.http_server.port",
        int,
        0,
        "HTTP RPC server port (0 = ephemeral)",
        in_defaults=False,
    )
    r(
        "fugue.rpc.http_server.timeout",
        float,
        30.0,
        "HTTP RPC request timeout (s)",
        in_defaults=False,
    )
    # daemon-hardening knobs of the HTTP server (rpc/http.py): a request
    # body over the cap is rejected with 413 before it is read into
    # memory; read_timeout bounds how long one request may keep a handler
    # thread blocked on a slow/stalled client socket
    r(
        FUGUE_CONF_RPC_HTTP_MAX_BODY,
        int,
        64 * 1024 * 1024,
        "max HTTP request body bytes (413 above; 0 = unlimited)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_RPC_HTTP_READ_TIMEOUT,
        float,
        30.0,
        "per-request socket read timeout of the HTTP server (s)",
        in_defaults=False,
    )
    # multi-tenant serving daemon (fugue_tpu/serve/): consumed by the
    # daemon via typed_conf_get with these registered defaults — declared
    # module-owned (not seeded) like the other fugue.rpc.http_server keys
    r(
        FUGUE_CONF_SERVE_HOST,
        str,
        "127.0.0.1",
        "bind host of the serving daemon's HTTP API",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_PORT,
        int,
        0,
        "serving daemon HTTP port (0 = ephemeral)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_MAX_CONCURRENT,
        int,
        4,
        "workflow submissions the daemon runs concurrently",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_SESSION_TTL,
        float,
        3600.0,
        "idle seconds before a serve session expires (0 = never)",
        in_defaults=False,
    )
    # sync submissions park an HTTP handler thread while they wait: the
    # cap bounds how long a wedged job can pin it — on expiry the call
    # returns the live job snapshot (still queued/running) and the
    # client polls /v1/jobs/<id> like an async submission
    r(
        FUGUE_CONF_SERVE_SYNC_WAIT,
        float,
        600.0,
        "max seconds a sync submit blocks before returning the job "
        "snapshot for polling (0 = unbounded)",
        in_defaults=False,
    )
    # per-tenant fair share of the device-memory budget: > 0 makes the
    # governor's spill ordering FAIR (the tenant most over
    # fraction * budget spills first, LRU within it) so one heavy serve
    # session cannot evict everyone else's persisted tables; 0 keeps the
    # original global LRU order
    r(
        FUGUE_CONF_SERVE_TENANT_BUDGET_FRACTION,
        float,
        0.0,
        "per-tenant fair share of the memory budget (0 = global LRU)",
        in_defaults=False,
    )
    # serving resilience (serve/state.py, serve/supervisor.py): a durable
    # state_path turns on the daemon's crash journal — the session
    # registry, per-session saved-table catalog (parquet artifacts with
    # sha256 fingerprints) and the async job journal are atomically
    # rewritten through engine.fs on every mutation, so a restarted
    # daemon rehydrates sessions, lazily reloads integrity-verified hot
    # tables, and resumes interrupted async jobs
    r(
        FUGUE_CONF_SERVE_STATE_PATH,
        str,
        "",
        "durable dir/URI for the daemon's state journal + hot-table "
        "artifacts ('' = ephemeral daemon, no crash recovery)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_DRAIN_TIMEOUT,
        float,
        30.0,
        "seconds in-flight jobs get to finish on stop(drain=True) before "
        "their tokens are cancelled and they are abandoned",
        in_defaults=False,
    )
    # backpressure & admission: overload answers 503/429 WITH Retry-After
    # instead of queueing unboundedly or blocking HTTP handler threads
    r(
        FUGUE_CONF_SERVE_MAX_QUEUE,
        int,
        256,
        "queued-job backlog over which new submissions get 503 + "
        "Retry-After (0 = unbounded queue)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_SESSION_MAX_JOBS,
        int,
        0,
        "per-session queued+running job cap; over it submissions get "
        "429 + Retry-After (0 = uncapped)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_MEMORY_REJECT,
        float,
        0.0,
        "device-tier fill fraction of the memory budget over which new "
        "submissions get 503 (0 = no memory-pressure rejection)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_SYNC_DEGRADE_DEPTH,
        int,
        0,
        "queued-job backlog at which sync submits degrade to async "
        "202 + job-id instead of parking an HTTP worker (0 = never)",
        in_defaults=False,
    )
    # engine supervisor: consecutive-failure circuit breakers per session
    # and per query fingerprint (deterministic workflow uuid) quarantine
    # poison queries with a structured error instead of burning retries;
    # a tripped breaker half-opens after the cooldown to probe recovery
    r(
        FUGUE_CONF_SERVE_BREAKER_THRESHOLD,
        int,
        5,
        "consecutive job failures that trip a session/query circuit "
        "breaker (0 = breakers off)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_BREAKER_COOLDOWN,
        float,
        30.0,
        "seconds a tripped breaker stays open before half-opening for "
        "one probe submission",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_HEARTBEAT_TIMEOUT,
        float,
        0.0,
        "seconds without a heartbeat before the supervisor cancels a "
        "running job as wedged (0 = runner timeouts only)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_JOB_TTL,
        float,
        600.0,
        "seconds a finished job keeps its result payload before TTL "
        "eviction drops it (status survives; 0 = keep until the record "
        "cap evicts the job)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_CLIENT_RETRIES,
        int,
        2,
        "ServeClient retries on transient transport failures and "
        "503/429 backpressure answers (honors server Retry-After)",
        in_defaults=False,
    )
    # daemon pre-warm (cold-start recovery): with a persistent
    # executable cache dir configured, a starting daemon loads the
    # cached executables matching its engine signature in the
    # background and /v1/health answers 503 state="warming" until the
    # warm finishes — so an LB routes the first query only when its
    # dispatch is compile-free (time_to_first_query becomes IO-bound)
    r(
        FUGUE_CONF_SERVE_PREWARM,
        bool,
        True,
        "pre-load persistent-cached executables at daemon start before "
        "/v1/health reports ready",
        in_defaults=False,
    )
    # serving fleet (fugue_tpu/serve/fleet.py): a front-tier router
    # spreading sessions across N daemon replicas with journal-based
    # migration — on replica death (or a planned drain for a rolling
    # restart) a survivor adopts the dead replica's journal, so sessions
    # and fingerprint-verified hot tables move without losing committed
    # saves. Replicas must share fugue.serve.state_path (and ideally the
    # fugue.optimize.cache.dir executable cache) — FWF504 warns when a
    # multi-replica conf lacks either.
    r(
        FUGUE_CONF_SERVE_FLEET_REPLICAS,
        int,
        0,
        "daemon replicas a ServeFleet runs behind the router (0/1 = "
        "single-daemon serving, no fleet)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_FLEET_HOST,
        str,
        "127.0.0.1",
        "bind host of the fleet router's HTTP front tier",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_FLEET_PORT,
        int,
        0,
        "fleet router HTTP port (0 = ephemeral)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_FLEET_HEALTH_INTERVAL,
        float,
        1.0,
        "seconds between the router's /v1/health polls of each replica",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_FLEET_DEATH_THRESHOLD,
        int,
        3,
        "consecutive health-poll/forward transport failures before the "
        "router declares a replica dead and fails its sessions over",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_FLEET_RESULT_CACHE_DIR,
        str,
        "",
        "dir/URI (via engine.fs) of the fleet's cross-replica result "
        "cache for pure queries, keyed by DAG fingerprint + table "
        "artifact sha256s ('' = off; ServeFleet defaults it under the "
        "shared state path)",
        in_defaults=False,
    )
    # per-replica device slices: when on and the pod has at least one
    # device per replica, the fleet partitions jax.devices() evenly and
    # sets each replica's fugue.jax.devices so every engine owns its own
    # sub-mesh (capacity model: qps x devices) instead of all replicas
    # sharing one global mesh.
    r(
        FUGUE_CONF_SERVE_FLEET_DEVICE_SLICES,
        bool,
        False,
        "give each fleet replica its own slice of jax.devices() via "
        "fugue.jax.devices (needs >= 1 device per replica)",
        in_defaults=False,
    )
    # overload-survival plane (ISSUE 18): the predictive scheduler
    # replaces FIFO job pickup with shortest-predicted-job-first inside
    # per-tenant fairness, costs each query from its fingerprint's
    # stats-store history (fugue.stats.path), and admits-or-queues on
    # PREDICTED device bytes against the governed memory budget instead
    # of rejecting on observed fill. fugue.serve.admission.* tune the
    # predictions; fugue.serve.autoscale.* drive the fleet autoscaler
    # (scale up on sustained queue/latency pressure, drain-then-retire
    # on idle via the same journal-adoption move as a rolling restart).
    r(
        FUGUE_CONF_SERVE_SCHEDULER,
        str,
        "fifo",
        "job scheduling policy: fifo | predictive (stats-store cost "
        "model, shortest-job-first within per-tenant fairness, "
        "priority/deadline submission fields)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_ADMISSION_MEMORY_FRACTION,
        float,
        0.8,
        "fraction of the governed device-memory budget the predictive "
        "scheduler plans into: a queued job whose predicted bytes would "
        "push the in-flight prediction over it waits for headroom "
        "instead of starting (0 = predicted-memory gating off)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_ADMISSION_MAX_WAIT,
        float,
        0.0,
        "seconds of predicted queue drain beyond which new submissions "
        "are shed in priority order with 503 + Retry-After sized from "
        "the predicted drain (0 = predictive shedding off)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_ADMISSION_DEFAULT_MS,
        float,
        250.0,
        "assumed wall milliseconds for a query fingerprint with no "
        "stats-store history",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_ADMISSION_DEFAULT_BYTES,
        int,
        32 * 1024 * 1024,
        "assumed peak device bytes for a query fingerprint with no "
        "stats-store history",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_AUTOSCALE_MAX_REPLICAS,
        int,
        0,
        "replica ceiling the fleet autoscaler may grow to (0 = "
        "autoscaler off; must exceed fugue.serve.fleet.replicas to "
        "ever scale up)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_AUTOSCALE_MIN_REPLICAS,
        int,
        1,
        "replica floor scale-down must never drain below",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_AUTOSCALE_INTERVAL,
        float,
        2.0,
        "seconds between autoscaler pressure samples",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_AUTOSCALE_UP_QUEUE,
        int,
        4,
        "mean queued jobs per replica that counts one sample as "
        "pressured",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_AUTOSCALE_UP_P99_MS,
        float,
        0.0,
        "fleet p99 job milliseconds that counts one sample as "
        "pressured (0 = queue-depth signal only)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_AUTOSCALE_SUSTAIN_TICKS,
        int,
        3,
        "consecutive pressured samples before a scale-up (one spike "
        "must not spawn a replica)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_AUTOSCALE_IDLE_TICKS,
        int,
        10,
        "consecutive idle samples (no queue, no running jobs) before "
        "drain-then-retire of the newest surplus replica",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_SERVE_AUTOSCALE_COOLDOWN,
        float,
        10.0,
        "seconds after any scale action during which the autoscaler "
        "only observes",
        in_defaults=False,
    )
    # cost-based DAG optimizer (fugue_tpu/optimize): the rewrite phase
    # running between schema propagation and execution. "auto" (default)
    # enables it for jax engines only; per-rule keys disable individual
    # rewrites. Rewrites NEVER change task uuids (clones pin them), so
    # deterministic checkpoints and manifest resume are unaffected.
    r(
        FUGUE_CONF_OPTIMIZE,
        str,
        "auto",
        "DAG rewrite phase: off | on | auto (jax engines only)",
    )
    r(FUGUE_CONF_OPTIMIZE_CSE, bool, True, "common-subplan elimination rule")
    r(
        FUGUE_CONF_OPTIMIZE_FILTER,
        bool,
        True,
        "filter pushdown past select/rename + parquet row-group pruning",
    )
    r(
        FUGUE_CONF_OPTIMIZE_FUSION,
        bool,
        True,
        "select/rename/filter chain fusion into one compiled program",
    )
    r(
        FUGUE_CONF_OPTIMIZE_PROJECTION,
        bool,
        True,
        "projection pushdown into the parquet load's narrow-load planner",
    )
    # process-wide plan & result cache (fugue_tpu/optimize/cache.py):
    # compiled jit program handles are ALWAYS shared across same-conf
    # engine instances; result_cache additionally serves
    # deterministically-checkpointed task artifacts from memory while
    # the artifact exists (opt-in: the artifact already gives cross-run
    # reuse, the memory tier is for hot repeated pipelines)
    r(
        FUGUE_CONF_OPTIMIZE_RESULT_CACHE,
        bool,
        False,
        "in-memory reuse of deterministically-checkpointed task results",
    )
    r(
        FUGUE_CONF_OPTIMIZE_CACHE_MAX_PROGRAMS,
        int,
        512,
        "LRU bound on process-wide cached compiled program handles",
    )
    r(
        FUGUE_CONF_OPTIMIZE_CACHE_MAX_ENTRIES,
        int,
        256,
        "LRU bound on process-wide cached result entries",
    )
    r(
        FUGUE_CONF_OPTIMIZE_CACHE_MAX_RESULT_BYTES,
        int,
        256 * 1024 * 1024,
        "byte bound on cached results (governed engines additionally "
        "clamp to a fraction of the HBM ledger budget)",
    )
    # the plan cache's DISK tier (fugue_tpu/optimize/exec_cache.py):
    # compiled executables are AOT-serialized through engine.fs under
    # this dir/URI, keyed by the plan signature (platform + mesh devices
    # + fugue.jax.* conf) plus the program key, fn source hash and
    # argument avals — so a FRESH PROCESS skips XLA compilation
    # entirely, and URI-capable storage lets fleet replicas share one
    # cache. Entries are version-stamped (jax/jaxlib/format rev); stale
    # or corrupt entries evict to a recompile, never an error. Takes
    # precedence over the deprecated fugue.jax.compile.cache alias.
    r(
        FUGUE_CONF_OPTIMIZE_CACHE_DIR,
        str,
        "",
        "dir/URI (via engine.fs) of the persistent compiled-executable "
        "cache ('' = disk tier off; overrides the deprecated "
        "fugue.jax.compile.cache alias)",
    )
    # serving daemon's cross-request query result cache: a resubmitted
    # identical pure query (same session, same table-catalog epoch, same
    # DAG uuid) answers from the cached payload with zero execution —
    # the "millions of users running similar queries" fast path
    r(
        FUGUE_CONF_SERVE_RESULT_CACHE,
        bool,
        True,
        "serving daemon cross-request result cache for pure queries",
        in_defaults=False,
    )
    # unified observability plane (fugue_tpu/obs): request-scoped span
    # tracing + metrics registry + Perfetto/Prometheus export. With
    # enabled=False every instrumentation site is an allocation-free
    # no-op (the hot-path contract the zero-overhead test enforces).
    # Module-owned like the serve keys: read via typed_conf_get, not
    # seeded into the global defaults.
    r(
        FUGUE_CONF_OBS_ENABLED,
        bool,
        False,
        "request-scoped span tracing: off = every instrumentation site "
        "is an allocation-free no-op",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_OBS_TRACE_PATH,
        str,
        "",
        "dir/URI (via engine.fs) for per-trace Chrome-trace JSON files "
        "loadable in Perfetto ('' = no trace files)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_OBS_SLOW_QUERY_MS,
        float,
        0.0,
        "jobs/runs slower than this log one structured record with "
        "their span breakdown (0 = off)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_OBS_SAMPLE_RATE,
        float,
        1.0,
        "fraction of eligible requests/runs that open a trace",
        in_defaults=False,
    )
    # per-task profiler (ISSUE 14): rows in/out, device bytes, compile/
    # execute/transfer split, queue wait, retries and cache events per
    # DAG task, surfaced as FugueWorkflowResult.profile() (EXPLAIN
    # ANALYZE). Needs fugue.obs.enabled for the span-derived phase
    # split — FWF505 warns about the silently inert combination.
    r(
        FUGUE_CONF_OBS_PROFILE,
        bool,
        False,
        "per-task runtime profiler (EXPLAIN ANALYZE); inert unless "
        "fugue.obs.enabled is also on",
        in_defaults=False,
    )
    # persisted runtime-statistics store (fugue_tpu/obs/stats_store.py):
    # profiled runs append per-task-uuid observed rows/bytes/timings
    # into a bounded ring per query fingerprint under this dir/URI via
    # engine.fs — the statistics the phase-2 cost model / adaptive
    # re-planning (ROADMAP item 1) will read. The serving daemon
    # defaults it to <fugue.serve.state_path>/stats.
    r(
        FUGUE_CONF_STATS_PATH,
        str,
        "",
        "dir/URI (via engine.fs) of the persisted runtime-statistics "
        "store ('' = off; serving defaults to <state_path>/stats)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_STATS_HISTORY,
        int,
        32,
        "observations kept per query fingerprint in the runtime-"
        "statistics store (bounded ring)",
        in_defaults=False,
    )
    # continuous execution (fugue_tpu/stream): a standing pipeline tails
    # new parquet files under fugue.stream.source through the fs layer
    # (mtime-then-name discovery order), folds each micro-batch into
    # device-resident accumulators carried ACROSS batches, and commits
    # an exactly-once progress manifest (consumed files + accumulator
    # snapshot) per batch. Module-owned (read via typed_conf_get, not
    # seeded); FWF506 warns about inert fugue.stream.* keys (no source)
    # and a standing pipeline without fugue.workflow.resume (no durable
    # progress manifest -> a restart refolds from scratch).
    r(
        FUGUE_CONF_STREAM_SOURCE,
        str,
        "",
        "dir/URI (via engine.fs) a standing pipeline tails for arriving "
        "parquet files ('' = no streaming source)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_STREAM_PATTERN,
        str,
        "*.parquet",
        "basename glob the tail source matches new files against",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_STREAM_INTERVAL,
        float,
        1.0,
        "seconds between a standing pipeline's discovery polls "
        "(0 = manual stepping only, no ticker thread)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_STREAM_WATERMARK_DELAY,
        float,
        0.0,
        "event-time lateness allowance: watermark = max event time seen "
        "- delay; a window emits only once the watermark passes its end",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_STREAM_MAX_FILES,
        int,
        0,
        "cap on files folded per micro-batch (0 = all newly discovered)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_STREAM_BATCH_ROWS,
        int,
        0,
        "rows per host chunk when folding one parquet file "
        "(0 = pyarrow's record-batch default)",
        in_defaults=False,
    )
    # versioned table storage (fugue_tpu/lake): snapshot-isolated tables
    # of immutable parquet data files + a _meta/ manifest log, committed
    # through an optimistic CAS on the next manifest slot. Module-owned
    # (read via typed_conf_get, not seeded); FWF507 warns about inert
    # fugue.lake.* keys and AS OF reads against non-lake paths.
    r(
        FUGUE_CONF_LAKE_COMMIT_RETRIES,
        int,
        10,
        "optimistic-commit attempts before a LakeCommitConflict "
        "propagates (each retry rebases on the new table head)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_LAKE_COMMIT_BACKOFF,
        float,
        0.05,
        "base seconds of linear backoff between lake commit retries "
        "(attempt k sleeps ~k*backoff with jitter)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_LAKE_COMPACT_TARGET_ROWS,
        int,
        1_000_000,
        "rows per rewritten data file when compaction coalesces "
        "streamed micro-batch files into larger ones",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_LAKE_SERVE_PATH,
        str,
        "",
        "base dir/URI for lake-backed serve tables: session save_table "
        "commits each materialized view as a shared versioned table "
        "under <path>/<name> any replica can query ('' = per-session "
        "parquet artifacts, the pre-lake behavior)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_LAKE_VERIFY,
        bool,
        False,
        "verify each data file's manifest-recorded sha256 on scan; a "
        "mismatch fails the read with LakeIntegrityError and counts "
        "fugue_lake_integrity_rejected (files committed before the "
        "checksum field skip verification)",
        in_defaults=False,
    )
    # runtime lock-order sanitizer (testing/locktrace.py): debug-only.
    # Off (the default), every tracked_lock() call returns a plain
    # threading lock — no wrapper, zero overhead. On, locks created
    # afterwards are name-registered and every acquisition is checked
    # for ordering inversions/potential deadlock cycles. Consumed by
    # the serving daemon at start and by tests; module-owned, not seeded.
    r(
        FUGUE_CONF_DEBUG_LOCK_SANITIZER,
        bool,
        False,
        "debug lock-order sanitizer: wrap locks created after arming and "
        "report acquisition-order inversions (off = zero overhead)",
        in_defaults=False,
    )
    # runtime retrace sentinel (testing/retrace.py): debug-only twin of
    # the static FJX jit-hazard lint plane (analysis/jitlint). Off (the
    # default), every dispatch pays one module-global read. On, each
    # ACTUAL XLA trace of an engine program is counted per program key;
    # exceeding the budget logs (or raises) a report carrying the Python
    # callsite and the differing argument aval. Consumed by the serving
    # daemon at start and by tests; module-owned, not seeded.
    r(
        FUGUE_CONF_DEBUG_RETRACE_SENTINEL,
        bool,
        False,
        "debug retrace sentinel: count XLA traces per jitted program key "
        "and report programs exceeding the trace budget with callsite + "
        "differing aval (off = zero overhead)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_DEBUG_RETRACE_SENTINEL_MAX_TRACES,
        int,
        4,
        "trace budget per jitted program key before the retrace sentinel "
        "reports a violation (only read when the sentinel is armed)",
        in_defaults=False,
    )
    r(
        FUGUE_CONF_DEBUG_RETRACE_SENTINEL_RAISE,
        bool,
        False,
        "raise RetraceBudgetExceeded on a retrace-sentinel violation "
        "instead of logging it (CI benches die at the first unstable "
        "program)",
        in_defaults=False,
    )


_declare_defaults()

_DEFAULT_CONF: Dict[str, Any] = {
    info.key: info.default
    for info in _CONF_REGISTRY.values()
    if info.in_defaults
}

_GLOBAL_CONF = ParamDict(_DEFAULT_CONF)


def register_global_conf(conf: Dict[str, Any], on_dup: int = ParamDict.OVERWRITE) -> None:
    """Register global configs readable by every engine/workflow created after."""
    _GLOBAL_CONF.update(conf, on_dup=on_dup)


FUGUE_GLOBAL_CONF = _GLOBAL_CONF
