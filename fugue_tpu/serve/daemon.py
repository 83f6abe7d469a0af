"""The serving daemon: ONE persistent engine, many concurrent tenants.

``ServeDaemon`` composes the repo's existing parts into a resident
server (ROADMAP open item #2):

- a single long-lived execution engine (default ``"jax"``) entered as a
  context for the daemon's whole lifetime, so per-run context push/pop
  from concurrent job threads never tears it down between requests;
- :class:`~fugue_tpu.serve.session.SessionManager` sessions whose saved
  tables live device-resident in the SQL engine's catalog under a
  per-session namespace (hot across requests, no re-ingest) and are
  claimed as the memory governor's *tenants* for fair-spill accounting;
- :class:`~fugue_tpu.serve.scheduler.JobScheduler` running up to
  ``fugue.serve.max_concurrent`` FugueSQL workflows concurrently against
  the shared engine with the workflow runner's timeout + cancellation
  machinery;
- :class:`~fugue_tpu.serve.http.ServeHTTPServer` exposing the JSON API
  below on the hardened HTTP layer.

Resilience plane (ISSUE 7):

- **durable state** — with ``fugue.serve.state_path`` set, sessions,
  hot-table fingerprints and async jobs journal through
  :class:`~fugue_tpu.serve.state.ServeStateJournal`; a restarted daemon
  rehydrates sessions, lazily reloads integrity-verified hot tables and
  resubmits interrupted async jobs under their original ids;
- **graceful drain** — ``stop(drain=True)`` (or SIGTERM via
  :meth:`install_signal_handlers`) flips healthy→draining: new
  submissions answer 503 + ``Retry-After`` while in-flight jobs run to
  the ``fugue.serve.drain_timeout`` deadline, then state is journaled
  and the engine context closes;
- **backpressure** — queue-depth (``fugue.serve.max_queue``),
  memory-pressure (``fugue.serve.memory_reject_fraction`` over the HBM
  ledger) and per-session caps (``fugue.serve.session_max_jobs``)
  answer 503/429 + ``Retry-After``; deep-queue sync submits degrade to
  async 202 + job-id (``fugue.serve.sync_degrade_depth``);
- **supervision** — per-job heartbeats with a wedged-job watchdog, and
  consecutive-failure circuit breakers per session and per query
  fingerprint (deterministic workflow uuid) that quarantine poison
  queries with a structured error.

HTTP API (all JSON; errors are structured payloads, never tracebacks)::

    POST   /v1/sessions                     {"ttl": seconds?}
    GET    /v1/sessions
    GET    /v1/sessions/<sid>
    POST   /v1/sessions/<sid>/close         (alias: DELETE /v1/sessions/<sid>)
    POST   /v1/sessions/<sid>/sql           {"sql": ..., "save_as"?: name,
                                             "mode"?: "sync"|"async",
                                             "timeout"?: s, "collect"?: bool,
                                             "limit"?: rows,
                                             "profile"?: bool (EXPLAIN
                                             ANALYZE via /profile),
                                             "explain"?: bool (static plan
                                             report, nothing executes)}
    GET    /v1/jobs/<jid>                   poll an async submission
    GET    /v1/jobs/<jid>/profile           per-task runtime profile
    POST   /v1/jobs/<jid>/cancel
    GET    /v1/status                       health, memory_stats, breakers,
                                            backpressure, recovery, jobs,
                                            uptime_secs, version,
                                            compile_cache
    GET    /v1/health                       200 healthy / 503 draining
    GET    /v1/metrics                      Prometheus text exposition

Observability plane (ISSUE 8): every route accepts/echoes
``X-Request-Id`` (generated when absent/unsafe) and, with
``fugue.obs.enabled``, runs under a request trace whose spans follow the
job through the workflow into engine compile/execute/transfer — exported
as Perfetto-loadable Chrome-trace JSON under ``fugue.obs.trace_path``;
jobs over ``fugue.obs.slow_query_ms`` log a structured span breakdown.
"""

import re
import signal
import threading
import time
import uuid
from contextlib import nullcontext
from typing import Any, Dict, List, Optional, Tuple

from fugue_tpu.constants import (
    FUGUE_CONF_SERVE_ADMISSION_DEFAULT_BYTES,
    FUGUE_CONF_SERVE_ADMISSION_DEFAULT_MS,
    FUGUE_CONF_SERVE_ADMISSION_MAX_WAIT,
    FUGUE_CONF_SERVE_ADMISSION_MEMORY_FRACTION,
    FUGUE_CONF_SERVE_FLEET_RESULT_CACHE_DIR,
    FUGUE_CONF_SERVE_PREWARM,
    FUGUE_CONF_SERVE_RESULT_CACHE,
    FUGUE_CONF_SERVE_SCHEDULER,
    FUGUE_CONF_SERVE_BREAKER_COOLDOWN,
    FUGUE_CONF_SERVE_BREAKER_THRESHOLD,
    FUGUE_CONF_SERVE_DRAIN_TIMEOUT,
    FUGUE_CONF_SERVE_HEARTBEAT_TIMEOUT,
    FUGUE_CONF_SERVE_HOST,
    FUGUE_CONF_SERVE_JOB_TTL,
    FUGUE_CONF_SERVE_MAX_CONCURRENT,
    FUGUE_CONF_SERVE_MAX_QUEUE,
    FUGUE_CONF_SERVE_MEMORY_REJECT,
    FUGUE_CONF_SERVE_PORT,
    FUGUE_CONF_SERVE_SESSION_MAX_JOBS,
    FUGUE_CONF_SERVE_SESSION_TTL,
    FUGUE_CONF_SERVE_STATE_PATH,
    FUGUE_CONF_SERVE_SYNC_DEGRADE_DEPTH,
    FUGUE_CONF_SERVE_SYNC_WAIT,
    FUGUE_CONF_STATS_HISTORY,
    FUGUE_CONF_STATS_PATH,
    typed_conf_get,
)
from fugue_tpu.execution.factory import make_execution_engine
from fugue_tpu.obs import (
    activate,
    current_span,
    finalize_trace,
    force_profiling,
    maybe_log_slow_query,
    obs_options,
    open_trace,
    start_span,
    suppress_tracing,
)
from fugue_tpu.rpc.http import structured_error
from fugue_tpu.serve.http import ServeHTTPServer
from fugue_tpu.serve.scheduler import (
    CANCELLED,
    ERROR,
    JobScheduler,
    ServeJob,
)
from fugue_tpu.serve.session import ServeSession, SessionManager
from fugue_tpu.serve.state import ServeStateJournal, make_journal
from fugue_tpu.serve.supervisor import (
    AdmissionError,
    BackpressureError,
    EngineSupervisor,
    HealthState,
    SessionBusyError,
    STOPPED,
)
from fugue_tpu.sql_frontend.workflow_sql import FugueSQLWorkflow
from fugue_tpu.testing.faults import fault_point
from fugue_tpu.testing.locktrace import (
    active_sanitizer,
    disable_lock_sanitizer,
    maybe_enable_from_conf,
    tracked_lock,
)
from fugue_tpu.testing.retrace import (
    active_retrace_sentinel,
    disable_retrace_sentinel,
)
from fugue_tpu.testing.retrace import (
    maybe_enable_from_conf as retrace_enable_from_conf,
)
from fugue_tpu.utils.params import ParamDict

_RESULT_YIELD = "serve_result"

# breaker accounting must not count a breaker's own rejections as fresh
# failures (that would extend a quarantine every time someone probes it)
_BREAKER_ERRORS = ("PoisonQueryError", "CircuitOpenError")

# X-Request-Id hygiene: the inbound header becomes a trace id (and so a
# trace FILENAME under fugue.obs.trace_path) — restrict it to a safe
# charset and length; anything else is replaced by a generated id
_REQUEST_ID_RE = re.compile(r"^[A-Za-z0-9._-]{1,64}$")

_REJECT_KINDS = (
    "draining",
    "queue_full",
    "memory_pressure",
    "session_cap",
    "breaker_open",
    "sync_degraded",
    "shed",
)
_FAULT_KINDS = (
    "runs",
    "retries",
    "recoveries",
    "degradations",
    "integrity_rejected",
    "resumed",
)


def clean_request_id(raw: Optional[str]) -> Optional[str]:
    """The inbound ``X-Request-Id`` if it is safe to echo/journal/use as
    a trace id; None (→ generate one) otherwise."""
    if raw is None:
        return None
    rid = str(raw).strip()
    return rid if _REQUEST_ID_RE.match(rid) else None


def new_request_id() -> str:
    return "req-" + uuid.uuid4().hex[:16]


class ServeDaemon:
    """A long-lived in-process serving daemon. Usable as a context
    manager; ``start()`` binds the HTTP API and returns the daemon."""

    def __init__(self, conf: Any = None, engine: Any = "jax"):
        # debug lock-order sanitizer: must arm BEFORE the engine/
        # scheduler/session locks below are constructed so they wrap.
        # Remember whether THIS daemon armed it — stop() disarms then,
        # so a later same-process daemon without the flag gets plain
        # locks again instead of reporting into a dead scope
        self._owns_sanitizer = (
            active_sanitizer() is None
            and maybe_enable_from_conf(ParamDict(conf)) is not None
        )
        # debug retrace sentinel: same arming parity — conf-armed BEFORE
        # the engine exists so the very first dispatch is watched, and
        # owned arming is disarmed on stop()/_hard_kill() so a later
        # same-process daemon without the flag runs unwatched instead of
        # reporting into this daemon's dead scope
        self._owns_retrace_sentinel = (
            active_retrace_sentinel() is None
            and retrace_enable_from_conf(ParamDict(conf)) is not None
        )
        self._engine = make_execution_engine(engine, ParamDict(conf))
        econf = self._engine.conf
        self._journal = make_journal(
            self._engine, typed_conf_get(econf, FUGUE_CONF_SERVE_STATE_PATH)
        )
        # runtime-statistics store (ISSUE 14): a journaled daemon
        # defaults fugue.stats.path to <state_path>/stats, so profiled
        # jobs persist per-task observations next to the journal (the
        # engine conf carries the key — the workflow layer's profiler
        # writes through the same shared store instance)
        if (
            self._journal is not None
            and not str(
                typed_conf_get(econf, FUGUE_CONF_STATS_PATH) or ""
            ).strip()
        ):
            econf[FUGUE_CONF_STATS_PATH] = self._engine.fs.join(
                self._journal.base_uri, "stats"
            )
        self._stats_store: Any = None
        stats_path = str(
            typed_conf_get(econf, FUGUE_CONF_STATS_PATH) or ""
        ).strip()
        if stats_path:
            from fugue_tpu.obs.stats_store import get_stats_store

            self._stats_store = get_stats_store(
                self._engine,
                stats_path,
                history=typed_conf_get(econf, FUGUE_CONF_STATS_HISTORY),
            )
        self._health = HealthState()
        self._supervisor = EngineSupervisor(
            typed_conf_get(econf, FUGUE_CONF_SERVE_BREAKER_THRESHOLD),
            typed_conf_get(econf, FUGUE_CONF_SERVE_BREAKER_COOLDOWN),
            heartbeat_timeout=typed_conf_get(
                econf, FUGUE_CONF_SERVE_HEARTBEAT_TIMEOUT
            ),
            log=self._engine.log,
        )
        self._sessions = SessionManager(
            self._engine,
            default_ttl=typed_conf_get(econf, FUGUE_CONF_SERVE_SESSION_TTL),
            journal=self._journal,
        )
        # predictive overload plane (ISSUE 18): under
        # fugue.serve.scheduler=predictive the scheduler plans against
        # stats-store cost predictions — shortest-job-first inside
        # per-tenant fairness, priority/deadline submission fields, and
        # a PREDICTED-memory admission gate replacing the observed-fill
        # rejection. fifo (default) keeps PR 6 behavior bit-for-bit.
        self._scheduler_policy = str(
            typed_conf_get(econf, FUGUE_CONF_SERVE_SCHEDULER) or "fifo"
        ).lower()
        self._admission: Any = None
        if self._scheduler_policy == "predictive":
            from fugue_tpu.serve.admission import make_admission

            self._admission = make_admission(
                self._stats_store,
                typed_conf_get(econf, FUGUE_CONF_SERVE_MAX_CONCURRENT),
                typed_conf_get(
                    econf, FUGUE_CONF_SERVE_ADMISSION_MEMORY_FRACTION
                ),
                typed_conf_get(econf, FUGUE_CONF_SERVE_ADMISSION_DEFAULT_MS),
                typed_conf_get(
                    econf, FUGUE_CONF_SERVE_ADMISSION_DEFAULT_BYTES
                ),
                budget_bytes_fn=self._memory_budget_bytes,
            )
        self._admission_max_wait = max(
            0.0,
            float(
                typed_conf_get(econf, FUGUE_CONF_SERVE_ADMISSION_MAX_WAIT)
            ),
        )
        self._scheduler = JobScheduler(
            self._execute_job,
            typed_conf_get(econf, FUGUE_CONF_SERVE_MAX_CONCURRENT),
            job_ttl=typed_conf_get(econf, FUGUE_CONF_SERVE_JOB_TTL),
            on_finish=self._job_finished,
            policy=self._scheduler_policy,
            admission=self._admission,
        )
        http_conf = ParamDict(econf)
        http_conf["fugue.rpc.http_server.host"] = typed_conf_get(
            econf, FUGUE_CONF_SERVE_HOST
        )
        http_conf["fugue.rpc.http_server.port"] = typed_conf_get(
            econf, FUGUE_CONF_SERVE_PORT
        )
        self._http = ServeHTTPServer(self, http_conf)
        self._sync_wait = typed_conf_get(econf, FUGUE_CONF_SERVE_SYNC_WAIT)
        self._drain_timeout = typed_conf_get(
            econf, FUGUE_CONF_SERVE_DRAIN_TIMEOUT
        )
        self._max_queue = typed_conf_get(econf, FUGUE_CONF_SERVE_MAX_QUEUE)
        self._session_max_jobs = typed_conf_get(
            econf, FUGUE_CONF_SERVE_SESSION_MAX_JOBS
        )
        self._memory_reject = typed_conf_get(
            econf, FUGUE_CONF_SERVE_MEMORY_REJECT
        )
        self._sync_degrade_depth = typed_conf_get(
            econf, FUGUE_CONF_SERVE_SYNC_DEGRADE_DEPTH
        )
        self._started = False
        self._started_at: Optional[float] = None
        self._recovery: Dict[str, int] = {
            "sessions": 0,
            "pipelines": 0,
            "jobs_resubmitted": 0,
            "jobs_failed_over": 0,
        }
        self._drain_result: Optional[Dict[str, int]] = None
        # ---- cold-start pre-warm (ISSUE 11) ------------------------------
        # with a persistent executable cache configured, start() loads
        # the engine's cached executables in the background and
        # /v1/health answers 503 state="warming" until done — so an LB
        # routes the first query only when its dispatch is compile-free.
        # Phase timings (journal-reload / cache-load) plus the FIRST
        # query's compile/dispatch split land in status()["recovery"].
        self._prewarm_on = bool(
            typed_conf_get(econf, FUGUE_CONF_SERVE_PREWARM)
        )
        self._warming = False
        self._prewarm_thread: Optional[threading.Thread] = None
        self._restart_phases: Dict[str, Any] = {}
        self._first_query: Optional[Dict[str, Any]] = None
        self._first_query_lock = tracked_lock(
            "serve.daemon.ServeDaemon._first_query_lock"
        )
        # ---- standing pipelines / materialized views (ISSUE 15) ----------
        # (session_id, name) -> MaterializedView. Registration journals
        # the SPEC into the session record; restart/adoption rebuilds
        # the objects and each pipeline's progress manifest restores
        # its exactly-once state. The lock only guards the dict —
        # stepping/refreshing never runs under it.
        self._views: Dict[Tuple[str, str], Any] = {}
        self._views_lock = tracked_lock(
            "serve.daemon.ServeDaemon._views_lock"
        )
        # ---- observability plane (ISSUE 8) -------------------------------
        # the daemon's counters live on the ENGINE's metrics registry
        # (one registry per daemon by construction), rendered at
        # GET /v1/metrics; the status() payload keeps its historical
        # dict shapes as views over the families. Children are
        # pre-touched so scrapes see the full label schema at zero.
        self._obs = obs_options(econf)
        metrics = self._engine.metrics
        self._m_reject = metrics.counter(
            "fugue_serve_rejections_total",
            "submissions shed by admission control, by reason",
            ["kind"],
        )
        for kind in _REJECT_KINDS:
            self._m_reject.labels(kind=kind)
        self._m_fault = metrics.counter(
            "fugue_serve_fault_events_total",
            "workflow fault-tolerance events aggregated over served jobs",
            ["kind"],
        )
        for kind in _FAULT_KINDS:
            self._m_fault.labels(kind=kind)
        self._m_requests = metrics.counter(
            "fugue_serve_requests_total",
            "HTTP API requests by route family and status",
            ["route", "status"],
        )
        self._m_request_secs = metrics.histogram(
            "fugue_serve_request_seconds",
            "HTTP API request latency by route family",
            ["route"],
        )
        self._m_job_secs = metrics.histogram(
            "fugue_serve_job_seconds",
            "job execution wall clock (start to terminal) by outcome",
            ["status"],
        )
        # cross-request result cache (ISSUE 10): a resubmitted PURE
        # query (same session, same table-catalog epoch, same DAG uuid)
        # answers from the process-wide plan cache with zero execution —
        # no Python planning, no device dispatch, no recompile
        from fugue_tpu.optimize import get_plan_cache

        self._plan_cache = get_plan_cache()
        self._result_cache_on = bool(
            typed_conf_get(econf, FUGUE_CONF_SERVE_RESULT_CACHE)
        )
        # fleet tier (ISSUE 13): an fs-backed result cache shared by
        # every replica, keyed by the DAG fingerprint + the session
        # tables' artifact sha256s — content-addressed, so a migrated
        # (or merely content-identical) session warm-starts on ANY
        # replica without re-executing
        self._fleet_result_dir = str(
            typed_conf_get(econf, FUGUE_CONF_SERVE_FLEET_RESULT_CACHE_DIR)
            or ""
        ).strip()
        if self._fleet_result_dir:
            try:
                self._engine.fs.makedirs(
                    self._fleet_result_dir, exist_ok=True
                )
            except Exception:
                self._engine.log.warning(
                    "fugue_tpu serve: fleet result-cache dir %s is not "
                    "writable; cross-replica result cache disabled",
                    self._fleet_result_dir,
                )
                self._fleet_result_dir = ""
        self._m_result_cache = metrics.counter(
            "fugue_serve_result_cache_total",
            "cross-request query result cache lookups by result",
            ["result"],
        )
        for kind in ("hit", "miss", "fs_hit", "fs_miss", "fs_store",
                     "fs_error"):
            self._m_result_cache.labels(result=kind)
        # registry counters are process-monotonic (Prometheus
        # semantics), but status()'s dict shapes are DAEMON-scoped like
        # the dicts they replaced: baseline a caller-owned engine's
        # prior counts so a fresh daemon starts its payload at zero
        self._reject_base = self._m_reject.as_int_dict()
        self._fault_base = self._m_fault.as_int_dict()
        metrics.add_collector(self._collect_serve_gauges)

    # ---- lifecycle -------------------------------------------------------
    @property
    def engine(self) -> Any:
        return self._engine

    @property
    def sessions(self) -> SessionManager:
        return self._sessions

    @property
    def scheduler(self) -> JobScheduler:
        return self._scheduler

    @property
    def supervisor(self) -> EngineSupervisor:
        return self._supervisor

    @property
    def journal(self) -> Any:
        return self._journal

    @property
    def health_state(self) -> str:
        return self._health.state

    @property
    def address(self) -> Tuple[str, int]:
        """(host, port) of the bound HTTP API (after ``start``)."""
        return self._http.address

    def start(self) -> "ServeDaemon":
        if self._started:
            return self
        # hold the engine for the daemon's lifetime: concurrent job runs
        # push/pop their own per-thread contexts on top and the count
        # never reaches zero, so the engine stays hot between requests.
        # retain (not as_context): the hold must release cleanly from a
        # drain thread or signal handler, and the daemon's engine must
        # never become the caller thread's ambient context engine
        self._engine.retain()
        # prewarm BEFORE the scheduler/recovery can run any job: the
        # once-per-(dir,sig) warm claim is taken synchronously on this
        # thread inside warm_executables, so a recovered job's
        # streamed-ingest first-batch hook can never win it and turn
        # the readiness gate into a no-op
        self._start_prewarm()
        self._scheduler.start()
        if self._journal is not None:
            t0 = time.monotonic()
            self._recover()
            self._restart_phases["journal_reload_secs"] = round(
                time.monotonic() - t0, 4
            )
        self._supervisor.tick_hooks = [
            self._sessions.sweep,
            self._scheduler.gc_payloads,
            self._sweep_views,
        ]
        if self._journal is not None:
            self._supervisor.tick_hooks.append(self._journal.maybe_flush)
        self._supervisor.start(
            self._scheduler.running_jobs, abandon=self._scheduler.abandon
        )
        self._http.start()
        self._started = True
        self._started_at = time.time()
        return self

    def _start_prewarm(self) -> None:
        """Kick the background executable pre-warm when the engine has a
        persistent cache configured: deserializing the cached programs
        overlaps the rest of startup, and /v1/health reports
        ``warming`` (503) until the warm lands, so
        ``restart_recovery.time_to_first_query`` is IO-bound, not
        compile-bound. A no-op for cache-less engines."""
        if not self._prewarm_on:
            return
        begin = getattr(self._engine, "try_begin_warm", None)
        # the claim is taken HERE, on the starting thread, before the
        # scheduler exists — a recovered job's ingest hook can only
        # find it already owned and stay out of the readiness gate
        work = begin() if begin is not None else None
        if work is None:
            return
        self._warming = True

        def _warm() -> None:
            t0 = time.monotonic()
            loaded = 0
            try:
                loaded = int(self._prewarm(work) or 0)
            except Exception as ex:  # warm is best-effort, never fatal
                self._engine.log.warning(
                    "fugue_tpu serve: executable pre-warm failed "
                    "(%s: %s); first queries will compile",
                    type(ex).__name__, ex,
                )
            finally:
                self._restart_phases["cache_load_secs"] = round(
                    time.monotonic() - t0, 4
                )
                self._restart_phases["prewarmed_executables"] = loaded
                self._warming = False

        # through the exec-cache spawner: its atexit join keeps an
        # interpreter exiting WITHOUT daemon.stop() from tearing down
        # XLA under a thread still mid-deserialize (C++ abort)
        from fugue_tpu.optimize.exec_cache import spawn_warm_thread

        self._prewarm_thread = spawn_warm_thread(_warm)

    def _prewarm(self, work: Any) -> int:
        """Run the already-claimed warm: load the engine-signature-
        matching disk-cache entries (the executables every journaled
        query fingerprint compiled before the restart persisted here).
        Split out so tests can gate it."""
        return int(work() or 0)

    @property
    def ready(self) -> bool:
        """Healthy AND past the executable pre-warm — what
        ``GET /v1/health`` keys its 200 on."""
        return self._health.healthy and not self._warming

    def _recover(self) -> None:
        """Rehydrate the prior daemon's journaled state: sessions come
        back (tables reload lazily on first access), interrupted async
        jobs resubmit under their original ids, and jobs whose session
        did not survive fail over with a structured error a poller can
        read."""
        data = self._journal.load()
        self._recovery["sessions"] = self._sessions.restore(
            data.get("sessions") or {}
        )
        # standing pipelines rebuild from their journaled specs; each
        # progress manifest restores the last committed micro-batch
        self._recovery["pipelines"] = self._restore_views(
            data.get("sessions") or {}
        )
        resubmitted, failed_over = self._resubmit_journaled_jobs(
            data.get("jobs") or {}, import_into_journal=False
        )
        self._recovery["jobs_resubmitted"] += resubmitted
        self._recovery["jobs_failed_over"] += failed_over

    def _resubmit_journaled_jobs(
        self, jobs: Dict[str, Dict[str, Any]], import_into_journal: bool
    ) -> Tuple[int, int]:
        """Resubmit interrupted journaled jobs under their ORIGINAL ids
        (idempotent: saves are overwrite-mode); jobs whose session did
        not survive fail over with a structured error a poller can read.
        ``import_into_journal`` (the fleet-adoption path) records each
        resubmitted job into THIS daemon's journal first — restart
        recovery skips that, its jobs are already journaled here.
        Returns (resubmitted, failed_over)."""
        resubmitted = failed_over = 0
        for jid, rec in sorted(jobs.items()):
            job = ServeJob(
                rec.get("session_id", ""),
                rec.get("sql", ""),
                save_as=rec.get("save_as"),
                timeout=float(rec.get("timeout", 0.0) or 0.0),
                collect=bool(rec.get("collect", True)),
                limit=int(rec.get("limit", 10_000)),
                job_id=jid,
                request_id=rec.get("request_id"),
                profile=bool(rec.get("profile", False)),
                priority=int(rec.get("priority", 0) or 0),
                deadline=float(rec.get("deadline", 0.0) or 0.0),
            )
            job.recovered = True
            if self._admission is not None:
                job.cost = self._admission.model.estimate_sql(job.sql)
            try:
                self._sessions.get(job.session_id)
                if import_into_journal:
                    self._journal.record_job(job)
                self._scheduler.submit(job)
                resubmitted += 1
            except AdmissionError as ex:
                # this daemon started draining mid-loop. Do NOT
                # terminalize the job ("session did not survive" would
                # be a lie) and do NOT abort the pass — the sessions
                # are already adopted here, so aborting would let the
                # router re-adopt the same source elsewhere and
                # double-own them. DEFER instead: the job record is
                # (or stays) in THIS journal, and the failover that
                # follows this daemon's drain migrates it onward with
                # the sessions it belongs to.
                if import_into_journal:
                    self._journal.record_job(job)
                self._engine.log.warning(
                    "fugue_tpu serve: job %s deferred during "
                    "recovery/adoption (%s); it rides the next "
                    "failover of this daemon's journal",
                    jid, ex,
                )
            except Exception as ex:
                job.error = structured_error(
                    KeyError(
                        f"session {job.session_id} did not survive the "
                        f"daemon restart ({type(ex).__name__}); the job "
                        "cannot be resumed"
                    )
                )
                job.finish(ERROR)
                self._scheduler.adopt(job)
                self._journal.finish_job(jid)
                failed_over += 1
        return resubmitted, failed_over

    def adopt_state(self, state_path: str) -> Dict[str, Any]:
        """Fleet failover/handoff hook (``POST /v1/admin/adopt``): adopt
        a dead or drained replica's journaled state. Its unexpired
        sessions rehydrate HERE under their original ids (hot tables
        reload lazily from the shared-fs artifacts after fingerprint
        verification — the adoption analog of restart recovery), its
        interrupted async jobs resubmit under their original job ids,
        and the source journal is atomically emptied so a restarted
        origin replica cannot double-own the moved sessions."""
        if self._journal is None:
            raise ValueError(
                "this daemon has no state journal "
                "(fugue.serve.state_path); it cannot adopt replica state"
            )
        if not self._health.healthy:
            raise BackpressureError(
                f"daemon is {self._health.state}; not adopting sessions",
                retry_after=1.0,
            )
        base = str(state_path or "").strip()
        if base == "" or base.rstrip("/") == self._journal.base_uri:
            raise ValueError(f"invalid adoption source {state_path!r}")
        fs = self._engine.fs
        # CAS fence (write_file_if_absent): exactly ONE of N racing
        # adopters proceeds past this line per journal; the losers get
        # AdoptionFencedError and back off without reading any state.
        # The fence clears with the journal in clear_state; on a raised
        # adoption it is released so a later failover can retry.
        ServeStateJournal.acquire_adoption_fence(
            fs, base, owner=self._journal.base_uri
        )
        try:
            return self._adopt_state_fenced(base, fs)
        except BaseException:
            ServeStateJournal.clear_adoption_fence(fs, base)
            raise

    def _adopt_state_fenced(
        self, base: str, fs: Any
    ) -> Dict[str, Any]:
        data = ServeStateJournal.read_state(fs, base, log=self._engine.log)
        adopted, expired = self._sessions.adopt(data["sessions"])
        # the adopted sessions' standing pipelines move with them: the
        # specs rode along in the imported records, and the progress
        # manifests (origin state dir, shared fs) resume exactly-once
        adopted_pipelines = self._restore_views(
            data["sessions"], only=set(adopted)
        )
        self._recovery["pipelines"] += adopted_pipelines
        resubmitted, failed_over = self._resubmit_journaled_jobs(
            data["jobs"], import_into_journal=True
        )
        source_cleared = True
        try:
            ServeStateJournal.clear_state(fs, base)
        except Exception as ex:
            source_cleared = False
            # the adoption stands; a not-cleared source is logged loudly
            # because a restarted origin replica would double-own
            self._engine.log.warning(
                "fugue_tpu serve: adopted state from %s but could not "
                "clear the source journal (%s: %s) — do not restart the "
                "origin replica against it",
                base, type(ex).__name__, ex,
            )
        self._recovery["jobs_resubmitted"] += resubmitted
        self._recovery["jobs_failed_over"] += failed_over
        adopted_stats = 0
        if self._stats_store is not None:
            # the origin's runtime statistics ride along with its
            # sessions: merge its <state>/stats rings into ours so the
            # adopted queries keep their observed-rows history
            try:
                adopted_stats = self._stats_store.adopt(
                    fs.join(base, "stats")
                )
            except Exception:  # pragma: no cover - stats are best-effort
                pass
        return {
            "sessions": adopted,
            "expired_sessions": expired,
            "pipelines": adopted_pipelines,
            "stats_fingerprints": adopted_stats,
            "jobs_resubmitted": resubmitted,
            "jobs_failed_over": failed_over,
            # False = the origin journal still holds the moved state:
            # the operator/fleet must clear it before restarting the
            # origin replica, or it double-owns the sessions
            "source_cleared": source_cleared,
        }

    def stop(self, drain: bool = False) -> None:
        """Stop serving. ``drain=False`` (default) keeps PR 6 semantics:
        HTTP down first, scheduler cancelled, sessions closed, engine
        context stopped. ``drain=True`` is the graceful path: the health
        state flips to *draining* (new submissions answer 503 +
        Retry-After while polling keeps working), in-flight jobs get
        ``fugue.serve.drain_timeout`` seconds to finish, stragglers are
        cancelled and abandoned, and the final state is journaled BEFORE
        the engine context closes."""
        if not self._started:
            return
        if drain:
            self._health.start_drain(self._drain_timeout)
            self._drain_result = self._scheduler.drain(self._drain_timeout)
        self._started = False
        self._join_prewarm()
        # a stopped daemon must not keep publishing gauges through a
        # caller-owned engine's registry (stale values, leaked refs)
        self._engine.metrics.remove_collector(self._collect_serve_gauges)
        self._stop_views()  # tickers off; progress manifests survive
        self._supervisor.stop()
        self._http.stop()
        self._scheduler.stop()
        if self._journal is not None:
            # journaled daemon: keep durable state for the next start;
            # write the final snapshot before the engine dies
            self._sessions.shutdown()
            self._journal.write()
        else:
            self._sessions.close_all()
        self._engine.release()
        self._health.transition(STOPPED)
        if self._owns_sanitizer:
            disable_lock_sanitizer()
            self._owns_sanitizer = False
        if self._owns_retrace_sentinel:
            disable_retrace_sentinel()
            self._owns_retrace_sentinel = False

    def _join_prewarm(self) -> None:
        """A stopping daemon must not leave the warm thread touching a
        released engine; bounded join (the thread is a daemon)."""
        t = self._prewarm_thread
        if t is not None and t.is_alive():
            t.join(timeout=5.0)

    def install_signal_handlers(self) -> None:
        """SIGTERM/SIGINT → graceful drain (``stop(drain=True)`` on a
        helper thread, so the signal handler returns immediately). Call
        from the main thread of a dedicated serve process; in-process
        embeddings (tests, benches) should call ``stop`` directly."""

        def _drain_on_signal(signum: int, frame: Any) -> None:
            threading.Thread(
                target=self.stop, kwargs={"drain": True}, daemon=True,
                name="fugue-serve-drain",
            ).start()

        signal.signal(signal.SIGTERM, _drain_on_signal)
        signal.signal(signal.SIGINT, _drain_on_signal)

    def _hard_kill(self) -> None:
        """Chaos/test hook: the closest an in-process harness gets to
        ``kill -9`` mid-flight. No drain, no final journal write (the
        journal is incrementally crash-durable by construction), workers
        killed via sentinels, catalog copies dropped (device state dies
        with the process), engine context closed."""
        if not self._started:
            return
        self._started = False
        self._join_prewarm()
        self._engine.metrics.remove_collector(self._collect_serve_gauges)
        self._stop_views()
        # scheduler FIRST: its first act is dropping the finish
        # observers, so a job completing while the rest of the teardown
        # runs can no longer clean its journal entry — a real kill -9
        # would not have run those callbacks either
        self._scheduler.kill()
        self._supervisor.stop()
        self._http.stop()
        self._sessions.shutdown()  # drops catalog copies, keeps journal
        self._engine.release()
        self._health.transition(STOPPED)
        # even the kill path disarms an owned sanitizer/sentinel: a
        # restarted in-process daemon must not report into this dead scope
        if self._owns_sanitizer:
            disable_lock_sanitizer()
            self._owns_sanitizer = False
        if self._owns_retrace_sentinel:
            disable_retrace_sentinel()
            self._owns_retrace_sentinel = False

    def __enter__(self) -> "ServeDaemon":
        return self.start()

    def __exit__(self, *args: Any) -> None:
        self.stop()

    # ---- operations (HTTP routes call these; tests/benches may too) ------
    def create_session(self, ttl: Optional[float] = None) -> ServeSession:
        self._reject_if_unhealthy()
        return self._sessions.create(ttl=ttl)

    def close_session(self, session_id: str) -> Dict[str, Any]:
        self._drop_session_views(session_id)
        dropped = self._sessions.close(session_id)
        return {"closed": session_id, "dropped_tables": dropped}

    # ---- standing pipelines / materialized views (ISSUE 15) --------------
    def register_pipeline(
        self, session_id: str, payload: Dict[str, Any], step: bool = True
    ) -> Dict[str, Any]:
        """Register a standing pipeline maintaining ``payload["name"]``
        as this session's continuously-refreshed materialized view. The
        spec is journaled into the session record (restart + adoption
        rebuild it); the progress manifest defaults under the durable
        state path so a rebuilt pipeline resumes exactly-once. An
        initial ``step`` folds any already-arrived files so the view is
        queryable immediately."""
        from fugue_tpu.stream.pipeline import PipelineSpec
        from fugue_tpu.stream.view import MaterializedView, view_progress_uri

        self._reject_if_unhealthy()
        session = self._sessions.get(session_id)
        spec = PipelineSpec.from_dict(payload)
        if spec.progress is None and self._journal is not None:
            spec.progress = view_progress_uri(
                self._engine.fs,
                self._journal.base_uri,
                session_id,
                spec.name,
            )
        key = (session_id, spec.name)
        with self._views_lock:
            if key in self._views:
                raise ValueError(
                    f"pipeline {spec.name!r} is already registered on "
                    f"session {session_id}"
                )
        view = MaterializedView(self._engine, session, spec)
        with self._views_lock:
            if key in self._views:  # lost a registration race
                view.stop()
                raise ValueError(
                    f"pipeline {spec.name!r} is already registered on "
                    f"session {session_id}"
                )
            self._views[key] = view
        if self._journal is not None:
            self._journal.record_pipeline(
                session_id, spec.name, spec.to_dict()
            )
        out: Dict[str, Any] = {
            "session_id": session_id,
            "name": spec.name,
            "progress": spec.progress,
            "interval": spec.interval,
        }
        # ticker FIRST: the registration stands even when the initial
        # step fails (bad first file, NULL keys) — the error rides the
        # response, the pipeline stays registered and keeps ticking
        # (the step rolled back, so a fixed source folds cleanly later)
        view.start()
        if step:
            try:
                out["report"] = view.step()
            except Exception as ex:
                self._engine.log.warning(
                    "fugue_tpu serve: initial step of pipeline %s.%s "
                    "failed (%s: %s); registration stands",
                    session_id, spec.name, type(ex).__name__, ex,
                )
                out["report"] = {
                    "pipeline": spec.name,
                    "error": f"{type(ex).__name__}: {ex}",
                }
        return out

    def _get_view(self, session_id: str, name: str) -> Any:
        self._sessions.get(session_id)  # 404 + touch
        with self._views_lock:
            view = self._views.get((session_id, name))
        if view is None:
            raise KeyError(
                f"no pipeline {name!r} registered on session {session_id}"
            )
        return view

    def list_pipelines(self, session_id: str) -> List[Dict[str, Any]]:
        self._sessions.get(session_id)
        with self._views_lock:
            views = [
                v for (sid, _), v in sorted(self._views.items())
                if sid == session_id
            ]
        return [v.describe() for v in views]

    def describe_pipeline(
        self, session_id: str, name: str
    ) -> Dict[str, Any]:
        return self._get_view(session_id, name).describe()

    def step_pipeline(
        self, session_id: str, name: str, force_refresh: bool = False
    ) -> Dict[str, Any]:
        """Run one micro-batch of a registered pipeline NOW (the manual
        complement of the interval ticker; concurrent steps coalesce)."""
        self._reject_if_unhealthy()
        return self._get_view(session_id, name).step(
            force_refresh=force_refresh
        )

    def remove_pipeline(
        self, session_id: str, name: str, drop_table: bool = False
    ) -> Dict[str, Any]:
        view = self._get_view(session_id, name)
        with self._views_lock:
            self._views.pop((session_id, name), None)
        view.remove(drop_table=drop_table)
        if self._journal is not None:
            self._journal.forget_pipeline(session_id, name)
        return {
            "removed": name,
            "session_id": session_id,
            "dropped_table": drop_table,
        }

    def _restore_views(
        self, journaled: Dict[str, Dict[str, Any]], only: Any = None
    ) -> int:
        """Rebuild pipeline objects from journaled session records (the
        restart/adoption path). Each pipeline's progress manifest
        restores its accumulator state; a batch whose commit landed but
        whose refresh never confirmed re-emits on its first step. Never
        raises — a broken spec loses one view, not the daemon."""
        from fugue_tpu.stream.pipeline import PipelineSpec
        from fugue_tpu.stream.view import MaterializedView

        restored = 0
        for sid, rec in sorted(journaled.items()):
            if only is not None and sid not in only:
                continue
            session = self._sessions.peek(sid)
            if session is None:
                continue
            for name, spec_dict in sorted(
                (rec.get("pipelines") or {}).items()
            ):
                key = (sid, name)
                with self._views_lock:
                    if key in self._views:
                        continue
                try:
                    view = MaterializedView(
                        self._engine, session,
                        PipelineSpec.from_dict(spec_dict),
                    )
                except Exception as ex:
                    self._engine.log.warning(
                        "fugue_tpu serve: could not restore pipeline "
                        "%s.%s (%s: %s); its journal record is kept",
                        sid, name, type(ex).__name__, ex,
                    )
                    continue
                with self._views_lock:
                    self._views[key] = view
                view.start()
                restored += 1
        return restored

    def _drop_session_views(self, session_id: str) -> None:
        """A closing session takes its views down with it (tickers
        stopped, progress manifests cleared; the journal records die
        with the session record)."""
        with self._views_lock:
            keys = [k for k in self._views if k[0] == session_id]
            views = [self._views.pop(k) for k in keys]
        for v in views:
            try:
                v.remove(drop_table=False)
            except Exception:  # pragma: no cover - best-effort cleanup
                pass

    def _sweep_views(self) -> None:
        """Supervisor tick hook: a view whose session expired (TTL
        sweep) must stop ticking — peek, never get, so the sweep itself
        cannot keep an abandoned session alive."""
        with self._views_lock:
            items = list(self._views.items())
        for (sid, name), view in items:
            if self._sessions.peek(sid) is not None:
                continue
            with self._views_lock:
                self._views.pop((sid, name), None)
            try:
                # remove, not stop: the expired session's journal record
                # (pipeline specs included) is gone, so an orphaned
                # progress manifest would sit on shared fs forever
                view.remove(drop_table=False)
            except Exception:  # pragma: no cover - best-effort cleanup
                pass

    def _stop_views(self) -> None:
        """Daemon shutdown: stop tickers, KEEP progress manifests and
        journal records — the next daemon on this state path rebuilds
        and resumes the pipelines."""
        with self._views_lock:
            views = list(self._views.values())
            self._views.clear()
        for v in views:
            try:
                v.stop()
            except Exception:  # pragma: no cover - best-effort cleanup
                pass

    def _memory_budget_bytes(self) -> int:
        """Governed device-byte budget (0 = ungoverned) — what the
        predictive admission gate plans its in-flight predictions
        against."""
        mem = getattr(self._engine, "memory_stats", None)
        if not isinstance(mem, dict) or not mem.get("enabled"):
            return 0
        return int(mem.get("budget_bytes") or 0)

    def memory_pressure(self) -> float:
        """Device-tier fill fraction of the governed budget (0.0 when
        ungoverned) — the admission controller's memory signal, read
        from the PR 4 ledger snapshot."""
        mem = getattr(self._engine, "memory_stats", None)
        if not isinstance(mem, dict) or not mem.get("enabled"):
            return 0.0
        budget = mem.get("budget_bytes") or 0
        if budget <= 0:
            return 0.0
        return float((mem.get("tiers") or {}).get("device", 0)) / budget

    def _count_reject(self, kind: str) -> None:
        self._m_reject.labels(kind=kind).inc()

    def _collect_serve_gauges(self) -> None:
        """Scrape-time collector: pull-model serve gauges (breaker
        states as labeled gauges, queue depth, memory pressure, uptime,
        live sessions) computed when the registry is read."""
        metrics = self._engine.metrics
        g = metrics.gauge(
            "fugue_serve_breaker_states",
            "circuit breakers currently in each state",
            ["state"],
        )
        for state, n in self._supervisor.breaker_state_counts().items():
            g.labels(state=state).set(n)
        metrics.gauge(
            "fugue_serve_breaker_trips",
            "total breaker trips since daemon start",
        ).labels().set(self._supervisor.breaker_stats()["trips"])
        metrics.gauge(
            "fugue_serve_queue_depth", "queued (not yet running) jobs"
        ).labels().set(self._scheduler.backlog())
        metrics.gauge(
            "fugue_serve_memory_pressure",
            "device-tier fill fraction of the governed memory budget",
        ).labels().set(self.memory_pressure())
        metrics.gauge(
            "fugue_serve_sessions", "live serve sessions"
        ).labels().set(self._sessions.count())
        if self._admission is not None:
            metrics.gauge(
                "fugue_serve_predicted_drain_seconds",
                "predicted seconds until the job backlog drains "
                "(predictive scheduler)",
            ).labels().set(self._scheduler.predicted_drain_secs())
            metrics.gauge(
                "fugue_serve_predicted_inflight_bytes",
                "sum of running jobs' predicted peak device bytes",
            ).labels().set(self._admission.inflight_bytes())
        metrics.gauge(
            "fugue_serve_uptime_seconds", "seconds since daemon start"
        ).labels().set(
            time.time() - self._started_at
            if self._started_at is not None
            else 0.0
        )

    def _reject_if_unhealthy(self) -> None:
        """503 + Retry-After while draining/stopping. Checked BEFORE the
        session lookup too: a stopping daemon tears sessions down while
        the health state is still draining, and a racing submission must
        see the retryable rejection, never a fail-fast 404."""
        if not self._health.healthy:
            self._count_reject("draining")
            raise BackpressureError(
                f"daemon is {self._health.state}; not accepting submissions",
                retry_after=max(1.0, self._health.drain_remaining()),
            )

    def _admit(self, session_id: str, priority: int = 0) -> None:
        """Admission control for one submission; raises an
        :class:`AdmissionError` subtype (503/429 + Retry-After) when the
        daemon must shed load instead of queueing it. The caller has
        already passed :meth:`_reject_if_unhealthy` (before its session
        lookup), so this starts at the load signals."""
        if self._max_queue > 0 and self._scheduler.backlog() >= self._max_queue:
            self._count_reject("queue_full")
            raise BackpressureError(
                f"job queue is full ({self._max_queue} queued)",
                retry_after=1.0,
            )
        if self._admission is not None and self._admission_max_wait > 0:
            # predictive shedding (ISSUE 18): when the backlog's
            # PREDICTED drain exceeds the configured wait, shed in
            # priority order — the overload ratio sets the priority
            # floor a submission must clear, so cheap excess load drops
            # first while important work keeps landing; Retry-After is
            # the predicted drain itself, so backed-off clients return
            # when the queue is actually expected to have room. Never
            # touches accepted (queued/running) work: shedding happens
            # strictly at the door.
            drain = self._scheduler.predicted_drain_secs()
            ratio = drain / self._admission_max_wait
            if ratio > 1.0 and int(priority) < int(ratio):
                self._count_reject("shed")
                raise BackpressureError(
                    f"predicted queue drain {drain:.2f}s exceeds the "
                    f"admission wait budget {self._admission_max_wait:.2f}s "
                    f"(overload x{ratio:.1f}); submissions below priority "
                    f"{int(ratio)} are shed",
                    retry_after=max(1.0, drain),
                )
        if self._memory_reject > 0 and self._admission is None:
            # reactive observed-fill rejection (PR 6). Under the
            # predictive policy this check is OFF by design: jobs are
            # admitted and QUEUED, and the scheduler's predicted-memory
            # gate holds them until the in-flight prediction has room —
            # admit-or-queue on prediction, not reject on observation.
            pressure = self.memory_pressure()
            if pressure >= self._memory_reject:
                self._count_reject("memory_pressure")
                raise BackpressureError(
                    f"device memory pressure {pressure:.2f} is over the "
                    f"admission threshold {self._memory_reject:.2f}",
                    retry_after=2.0,
                )
        if (
            self._session_max_jobs > 0
            and self._scheduler.active_count(session_id)
            >= self._session_max_jobs
        ):
            self._count_reject("session_cap")
            raise SessionBusyError(
                f"session {session_id} already has "
                f"{self._session_max_jobs} jobs queued/running",
                retry_after=1.0,
            )
        try:
            self._supervisor.admit_session(session_id)
        except AdmissionError:
            self._count_reject("breaker_open")
            raise

    def submit(
        self,
        session_id: str,
        sql: str,
        save_as: Optional[str] = None,
        wait: bool = True,
        timeout: float = 0.0,
        collect: bool = True,
        limit: int = 10_000,
        request_id: Optional[str] = None,
        profile: bool = False,
        priority: int = 0,
        deadline: float = 0.0,
    ) -> ServeJob:
        self._reject_if_unhealthy()
        self._sessions.get(session_id)  # 404 early + touches the session
        self._admit(session_id, priority=priority)
        job = ServeJob(
            session_id,
            sql,
            save_as=save_as,
            timeout=timeout,
            collect=collect,
            limit=limit,
            request_id=request_id,
            profile=profile,
            priority=priority,
            deadline=deadline,
        )
        if self._admission is not None:
            # submit-time cost: stats-store-backed for repeat queries
            # (the execution path feeds the sql→fingerprint map),
            # registered defaults for first-timers
            job.cost = self._admission.model.estimate_sql(sql)
        # under an active request trace the job gets its serve.job span
        # NOW: queue wait is inside it, so traces attribute time spent
        # queued behind the scheduler separately from execution
        cur = current_span()
        if cur is not None:
            job.obs_trace = cur.trace
            job.obs_span = cur.trace.start_span(
                "serve.job",
                cur,
                {"job_id": job.job_id, "session_id": session_id},
            )
        if not wait and self._journal is not None:
            # journal BEFORE the queue: a crash between accept and
            # dispatch still resumes the job on restart
            self._journal.record_job(job)
        try:
            self._scheduler.submit(job)
        except Exception:
            if not wait and self._journal is not None:
                self._journal.finish_job(job.job_id)
            # _admit may have claimed a half-open probe slot: release it
            self._supervisor.note_cancelled(session_id, None)
            if job.obs_span is not None:
                job.obs_span.set_attr(status="rejected")
                job.obs_span.finish()
            raise
        if wait:
            # bounded: a wedged job must not pin the caller (an HTTP
            # handler thread) forever — on expiry the live snapshot goes
            # back (status still queued/running) and the client polls
            # /v1/jobs/<id> exactly like an async submission
            job.done_event.wait(
                timeout=self._sync_wait if self._sync_wait > 0 else None
            )
        return job

    def status(self) -> Dict[str, Any]:
        self._sessions.sweep()
        engine_stats: Dict[str, Any] = {
            "type": type(self._engine).__name__,
            "parallelism": self._engine.get_current_parallelism(),
        }
        mem = getattr(self._engine, "memory_stats", None)
        if isinstance(mem, dict):
            engine_stats["memory"] = mem
        fallbacks = getattr(self._engine, "fallbacks", None)
        if isinstance(fallbacks, dict):
            engine_stats["fallbacks"] = fallbacks
        # historical dict shapes, now views over the metric families
        # (minus the pre-daemon baseline on caller-owned engines)
        fault_totals = {
            k: v - self._fault_base.get(k, 0)
            for k, v in self._m_fault.as_int_dict().items()
        }
        reject_totals = {
            k: v - self._reject_base.get(k, 0)
            for k, v in self._m_reject.as_int_dict().items()
        }
        fault_totals["integrity_rejected"] += (
            self._sessions.integrity_rejected()
        )
        counts = self._scheduler.counts()
        health = self._health.describe()
        if self._health.state != "healthy":
            health["jobs_in_flight"] = counts["queued"] + counts["running"]
            if self._drain_result is not None:
                health["drain_result"] = dict(self._drain_result)
        uptime = (
            round(time.time() - self._started_at, 3)
            if self._started_at is not None
            else 0.0
        )
        from fugue_tpu import __version__

        # ISSUE 10: compile_cache reads the plan cache's EXACT
        # program-handle lookup counters (hit = a compiled handle was
        # reused) instead of the per-dispatch jax-cache-growth heuristic
        compile_cache = getattr(
            self._engine,
            "plan_cache_stats",
            getattr(self._engine, "compile_cache_stats", None),
        )
        plan_cache = dict(self._plan_cache.stats())
        plan_cache["serve_result"] = {
            str(k): v for k, v in self._m_result_cache.as_int_dict().items()
        }
        out: Dict[str, Any] = {
            "uptime_seconds": uptime,
            "uptime_secs": uptime,
            "version": __version__,
            "compile_cache": (
                dict(compile_cache)
                if isinstance(compile_cache, dict)
                else {"hits": 0, "misses": 0}
            ),
            "plan_cache": plan_cache,
            "health": health,
            "engine": engine_stats,
            "sessions": {
                "count": self._sessions.count(),
                "active": self._sessions.describe(),
            },
            "jobs": counts,
            "fault_stats": fault_totals,
            "backpressure": {
                "queue_depth": self._scheduler.backlog(),
                "max_queue": self._max_queue,
                "memory_pressure": round(self.memory_pressure(), 4),
                "rejections": reject_totals,
                "scheduler": self._scheduler_policy,
            },
            "supervisor": {
                "breakers": self._supervisor.breaker_stats(),
                "wedged_jobs_cancelled": self._supervisor.wedged_jobs,
                "heartbeat_timeout": self._supervisor.heartbeat_timeout,
            },
        }
        if self._admission is not None:
            admission = self._admission.describe()
            admission["max_predicted_wait"] = self._admission_max_wait
            out["admission"] = admission
        if self._journal is not None:
            out["durable"] = self._journal.describe()
            out["recovery"] = dict(self._recovery)
        if self._stats_store is not None:
            out["stats_store"] = self._stats_store.describe()
        if self._restart_phases or self._first_query:
            # time_to_first_query phase split (ISSUE 11): journal-reload
            # and cache-load from startup, compile/dispatch from the
            # engine's dispatch clock over the first executed query.
            # A SIBLING of "recovery" (whose keys are a stable contract)
            out["cold_start"] = {
                "phases": dict(self._restart_phases),
                "first_query": dict(self._first_query or {}),
                "warming": self._warming,
            }
        if getattr(self._engine, "_exec_enabled", False):
            out["exec_cache"] = self._engine.exec_cache_stats
        if getattr(self._engine, "is_degraded", False):
            out["device_recovery"] = {
                "lost_devices": list(self._engine.lost_devices),
                "surviving_devices": int(
                    self._engine.surviving_device_count
                ),
                "recoveries": int(self._engine.device_recoveries),
            }
        return out

    # ---- job execution (scheduler worker threads) ------------------------
    def _execute_job(self, job: ServeJob) -> Dict[str, Any]:
        # re-attach the submitting request's trace on THIS worker
        # thread: everything below (workflow.run → tasks → attempts →
        # engine compile/execute/transfer) lands under the job's span.
        # A job whose request LOST the sampling draw runs suppressed, so
        # the workflow layer does not re-draw and export an
        # uncorrelated trace of its own.
        if self._obs.enabled and job.obs_span is None:
            scope = suppress_tracing()
        else:
            scope = activate(job.obs_span)
        # queued_us: the scheduler wait (try_start stamped started_at at
        # pickup), carried by the span that ends on this thread
        queued_us = round((job.started_at - job.submitted_at) * 1e6)
        with scope:
            with start_span(
                "serve.execute", job_id=job.job_id, queued_us=queued_us
            ):
                return self._timed_execute(job)

    def _timed_execute(self, job: ServeJob) -> Dict[str, Any]:
        """Record the FIRST executed query's wall clock split into
        compile / dispatch / disk-load (engine dispatch clock deltas)
        plus its XLA compile count — the ``time_to_first_query``
        evidence the restart-recovery bench reads from /v1/status."""
        if self._first_query is not None or not hasattr(
            self._engine, "dispatch_time_stats"
        ):
            return self._execute_job_impl(job)
        with self._first_query_lock:
            # claim without holding the lock across execution (a held
            # lock would serialize every job queued behind the first)
            if self._first_query is not None:
                claimed = False
            else:
                claimed = True
                self._first_query = {}  # claimed; filled below
        if not claimed:
            return self._execute_job_impl(job)
        d0 = self._engine.dispatch_time_stats
        c0 = self._engine.compile_cache_stats
        t0 = time.monotonic()
        try:
            return self._execute_job_impl(job)
        finally:
            d1 = self._engine.dispatch_time_stats
            c1 = self._engine.compile_cache_stats
            self._first_query = {
                "total_secs": round(time.monotonic() - t0, 4),
                "compile_secs": round(d1["compile"] - d0["compile"], 4),
                "dispatch_secs": round(d1["execute"] - d0["execute"], 4),
                "disk_load_secs": round(
                    d1["disk_load"] - d0["disk_load"], 4
                ),
                "xla_compiles": c1["misses"] - c0["misses"],
            }

    def _execute_job_impl(self, job: ServeJob) -> Dict[str, Any]:
        job.beat()
        session = self._sessions.get(job.session_id)
        dag = FugueSQLWorkflow()
        # snapshot the epoch BEFORE the table frames: a concurrent
        # save_table between the snapshot and the key build must make
        # this job's payload land under the OLD epoch (never served
        # again), not under the new one with pre-save data
        cache_epoch = session.cache_epoch
        # content keys snapshot with the epoch: a save racing this job
        # leaves the payload under the PRE-save keys (equivalent to the
        # job having run just before the save), never the new ones
        pre_content_keys = (
            session.table_content_keys() if self._fleet_result_dir else None
        )
        sources = session.table_frames()
        try:
            dag._sql(job.sql, {}, **sources)
        except Exception:
            # the query never compiled, so there is no DAG uuid to key
            # the breaker on — fall back to a deterministic text hash so
            # repeat-submitting a compile-poison query still quarantines
            from fugue_tpu.utils.hash import to_uuid

            job.fingerprint = to_uuid(
                "serve.compile", sorted(sources), job.sql
            )
            self._supervisor.admit_query(job.fingerprint)
            raise
        # the DAG's deterministic uuid (built from task uuids) is the
        # breaker's query fingerprint: same query over the same session
        # tables -> same key, across submissions and daemon restarts
        job.fingerprint = dag.__uuid__()
        if self._admission is not None:
            # cost-model feedback: the NEXT submission of this SQL text
            # resolves to this fingerprint's stats-store history at
            # admission time, before any compilation
            from fugue_tpu.serve.admission import sql_cost_key

            self._admission.model.note_fingerprint(
                sql_cost_key(job.sql), job.fingerprint
            )
        self._supervisor.admit_query(job.fingerprint)
        has_result = dag.last_df is not None
        # cross-request result cache: only PURE queries (deterministic
        # builtins, no output tasks, no user yields, no save_as) are
        # eligible — a cached payload must never skip a side effect.
        # The key folds the session id and its catalog epoch so another
        # session's same-shaped tables or a post-save resubmission can
        # never be served the wrong payload.
        cache_key = None
        fleet_cache_uri = None
        if (
            (self._result_cache_on or self._fleet_result_dir)
            and has_result
            and job.save_as is None
            and job.collect
            and len(dag.yields) == 0
            # a profile-requested job must actually EXECUTE (EXPLAIN
            # ANALYZE measures a run, a cached payload has no profile)
            and not job.profile_requested
        ):
            from fugue_tpu.optimize.rewrite import tasks_are_pure

            # session table frames only change via save_table, which
            # bumps cache_epoch in this key: frame inputs are stable
            if tasks_are_pure(dag.tasks, frame_inputs_stable=True):
                if self._result_cache_on:
                    cache_key = (
                        "serve",
                        job.session_id,
                        cache_epoch,
                        job.fingerprint,
                        job.limit,
                    )
                # fleet tier: content-addressed (DAG fingerprint + the
                # tables' artifact sha256s), so the key is valid on ANY
                # replica and for ANY session with identical content —
                # the cross-replica warm-start path. Sessions with an
                # unverifiable table (no durable artifact) are ineligible
                if self._fleet_result_dir and pre_content_keys is not None:
                    from fugue_tpu.utils.hash import to_uuid

                    fleet_cache_uri = self._engine.fs.join(
                        self._fleet_result_dir,
                        to_uuid(
                            "serve.fleet.result",
                            job.fingerprint,
                            str(job.limit),
                            pre_content_keys,
                        )
                        + ".json",
                    )
        if cache_key is not None:
            cached = self._plan_cache.get_result(cache_key)
            if cached is not None:
                self._m_result_cache.labels(result="hit").inc()
                session.touch()
                payload = dict(cached)
                if "result" in payload:
                    payload["result"] = dict(payload["result"])
                return payload
            self._m_result_cache.labels(result="miss").inc()
        if fleet_cache_uri is not None:
            from fugue_tpu.workflow.manifest import read_json

            entry = read_json(self._engine.fs, fleet_cache_uri)
            if isinstance(entry, dict) and isinstance(
                entry.get("payload"), dict
            ):
                self._m_result_cache.labels(result="fs_hit").inc()
                session.touch()
                return dict(entry["payload"])
            self._m_result_cache.labels(result="fs_miss").inc()
        if has_result:
            dag.last_df.yield_dataframe_as(_RESULT_YIELD)
        gov = getattr(self._engine, "memory_governor", None)
        # tenant_scope is THREAD-local: it covers the run's serial task
        # execution (the inner runner defaults to concurrency 1, in
        # thread) and this thread's save/collect materializations; a
        # parallel inner runner's worker threads are outside it, which
        # is fine — durable ownership comes from assign_tenant at
        # save_table time, and unsaved frames die with the job anyway
        scope = (
            gov.tenant_scope(job.session_id)
            if gov is not None
            else nullcontext()
        )
        profile_scope = (
            force_profiling() if job.profile_requested else nullcontext()
        )
        with scope:
            with profile_scope:
                wres = dag.run(self._engine, cancel_token=job.token)
            job.beat()
            # per-task runtime profile (EXPLAIN ANALYZE): present when
            # the job requested it or daemon conf profiles every run —
            # served at GET /v1/jobs/<id>/profile; the workflow layer
            # already persisted the observation into the stats store
            job.profile = wres.profile()
            self._note_fault_stats(wres.fault_stats)
            payload: Dict[str, Any] = {
                "yields": sorted(
                    k for k in dag.yields if k != _RESULT_YIELD
                ),
            }
            if not has_result:
                return payload
            df = wres[_RESULT_YIELD]
            if job.save_as is not None:
                session.save_table(job.save_as, df)
                job.beat()
                payload["saved_as"] = job.save_as
            if job.collect:
                from fugue_tpu.workflow.fault import engine_dispatch_guard

                # head() on a device frame reads back through device
                # programs: serialize with concurrent jobs; the job's
                # token makes the wait cancellable
                with engine_dispatch_guard(self._engine, job.token):
                    local = df.head(job.limit + 1)
                job.beat()
                with start_span("serve.encode"):
                    rows = local.as_array(type_safe=True)
                    truncated = len(rows) > job.limit
                    payload["result"] = {
                        "columns": list(df.schema.names),
                        "types": str(df.schema),
                        "rows": rows[: job.limit],
                        "row_count": min(len(rows), job.limit),
                        "truncated": truncated,
                    }
        if cache_key is not None:
            result = payload.get("result") or {}
            nbytes = 64 + 16 * len(result.get("rows") or []) * max(
                1, len(result.get("columns") or [])
            )
            stored = dict(payload)
            if "result" in stored:
                stored["result"] = dict(stored["result"])
            self._plan_cache.put_result(
                cache_key, stored, nbytes, tag=job.session_id
            )
        if fleet_cache_uri is not None:
            self._store_fleet_result(
                session, pre_content_keys, fleet_cache_uri, payload
            )
        session.touch()
        return payload

    def _store_fleet_result(
        self,
        session: ServeSession,
        pre_content_keys: Any,
        uri: str,
        payload: Dict[str, Any],
    ) -> None:
        """Best-effort store into the fleet's shared fs result cache —
        only when the session's table content is STILL what the key was
        computed from (a save racing the run must not publish new data
        under the old content keys). Failures count, never raise."""
        try:
            if session.table_content_keys() != pre_content_keys:
                return
            from fugue_tpu.serve.http import dumps
            from fugue_tpu.workflow.manifest import atomic_json_write

            # json-roundtrip through the serve encoder: result cells may
            # be numpy/temporal scalars the plain encoder rejects, and
            # an fs entry must read back exactly like an HTTP payload
            import json as _json

            normalized = _json.loads(dumps(payload).decode("utf-8"))
            atomic_json_write(
                self._engine.fs, uri,
                {"saved_at": time.time(), "payload": normalized},
            )
            self._m_result_cache.labels(result="fs_store").inc()
        except Exception as ex:
            self._m_result_cache.labels(result="fs_error").inc()
            self._engine.log.warning(
                "fugue_tpu serve: fleet result-cache store to %s failed "
                "(%s: %s); serving continues",
                uri, type(ex).__name__, ex,
            )

    def _job_finished(self, job: ServeJob) -> None:
        """Scheduler ``on_finish`` observer: job-journal cleanup,
        observability settlement (span end, latency histogram,
        slow-query log, trace export) and breaker accounting
        (cancellations are neutral; a breaker's own rejection never
        counts as a fresh failure)."""
        if self._journal is not None:
            self._journal.finish_job(job.job_id)
        self._obs_job_finished(job)
        if job.status == CANCELLED:
            # verdict-free for the breakers — but the job may have held
            # a half-open probe slot, which must go back
            self._supervisor.note_cancelled(job.session_id, job.fingerprint)
            return
        err_type = (job.error or {}).get("error")
        if err_type in _BREAKER_ERRORS:
            # a breaker's own rejection is verdict-free — but the
            # submit-time session admission may still hold a half-open
            # probe slot, which must go back (the query-fingerprint
            # breaker refused, so it claimed nothing)
            self._supervisor.note_cancelled(job.session_id, None)
            return
        self._supervisor.note_result(
            job.session_id, job.fingerprint, failed=job.status == ERROR
        )

    def _note_fault_stats(self, stats: Dict[str, Any]) -> None:
        self._m_fault.labels(kind="runs").inc()
        for key in (
            "retries", "recoveries", "degradations",
            "integrity_rejected",
        ):
            n = sum((stats.get(key) or {}).values())
            if n:
                self._m_fault.labels(kind=key).inc(n)
        resumed = len(stats.get("resumed") or [])
        if resumed:
            self._m_fault.labels(kind="resumed").inc(resumed)

    def _obs_job_finished(self, job: ServeJob) -> None:
        """Settle one finished job's observability: latency histogram,
        span end + trace export, slow-query record. Best-effort — never
        raises into the scheduler's finish path."""
        try:
            duration = None
            if job.started_at is not None and job.finished_at is not None:
                duration = job.finished_at - job.started_at
                self._m_job_secs.labels(status=job.status).observe(duration)
            if job.obs_span is not None:
                job.obs_span.set_attr(status=job.status)
                job.obs_span.finish()
            if duration is not None:
                maybe_log_slow_query(
                    job.obs_trace,
                    duration * 1000.0,
                    self._obs.slow_query_ms,
                    log=self._engine.log,
                    registry=self._engine.metrics,
                    # profiled jobs name their top-3 most expensive
                    # tasks (name, callsite, phase split) in the record
                    profile=job.profile,
                    job_id=job.job_id,
                    session_id=job.session_id,
                    request_id=job.request_id,
                    status=job.status,
                )
            if job.obs_trace is not None:
                # export when this job was the LAST open piece of its
                # request trace (async submissions; sync ones usually
                # export at HTTP response time)
                finalize_trace(
                    job.obs_trace,
                    self._obs,
                    fs=self._engine.fs,
                    log=self._engine.log,
                    registry=self._engine.metrics,
                    finish_root=False,
                )
        except Exception:  # pragma: no cover - observability best-effort
            pass

    # ---- HTTP routing ----------------------------------------------------
    def render_metrics(self) -> str:
        """The engine registry as Prometheus text exposition — the body
        of ``GET /v1/metrics``."""
        return self._engine.metrics.render()

    @staticmethod
    def _route_family(path: str) -> str:
        """Bounded-cardinality route label for request metrics: the
        first path segment under /v1 (health/status/metrics/sessions/
        jobs), never raw ids."""
        parts = [p for p in path.split("?", 1)[0].split("/") if p]
        if len(parts) >= 2 and parts[0] == "v1":
            return parts[1]
        return "unknown"

    def handle_api(
        self,
        method: str,
        path: str,
        payload: Dict[str, Any],
        request_id: Optional[str] = None,
    ) -> Tuple[int, Dict[str, Any], Dict[str, str]]:
        """Route one API request; returns (status, JSON-safe response,
        extra headers). Never raises: handler failures become structured
        error payloads (KeyError -> 404, admission/backpressure -> the
        error's own status + Retry-After header, bad input -> 400, the
        rest -> 500). Every response carries ``X-Request-Id`` — the
        (sanitized) inbound header or a generated id — and, with
        ``fugue.obs.enabled``, the request runs under a trace root whose
        id IS the correlation id."""
        rid = clean_request_id(request_id) or new_request_id()
        trace, root = open_trace(
            self._obs,
            "http.request",
            trace_id=rid,
            request_id=rid,
            method=method,
        )
        t0 = time.monotonic()
        status = 500
        try:
            with activate(root):
                status, resp, headers = self._handle(
                    method, path, payload, rid
                )
        finally:
            elapsed = time.monotonic() - t0
            if root is not None:
                root.set_attr(status=status)
                root.finish()
            route = self._route_family(path)
            self._m_requests.labels(route=route, status=str(status)).inc()
            self._m_request_secs.labels(route=route).observe(elapsed)
            if trace is not None:
                finalize_trace(
                    trace,
                    self._obs,
                    fs=self._engine.fs,
                    log=self._engine.log,
                    registry=self._engine.metrics,
                    finish_root=False,
                )
        out_headers = dict(headers)
        out_headers["X-Request-Id"] = rid
        return status, resp, out_headers

    def _handle(
        self,
        method: str,
        path: str,
        payload: Dict[str, Any],
        request_id: Optional[str] = None,
    ) -> Tuple[int, Dict[str, Any], Dict[str, str]]:
        try:
            fault_point("serve.http", f"{method} {path}")
            out = self._route(method, path, payload, request_id)
            if len(out) == 2:
                status, resp = out  # type: ignore[misc]
                return status, resp, {}
            return out  # type: ignore[return-value]
        except KeyError as ex:
            return 404, {"error": structured_error(ex)}, {}
        except AdmissionError as ex:
            return (
                ex.status,
                {
                    "error": structured_error(ex),
                    "retry_after": ex.retry_after,
                },
                {"Retry-After": str(max(1, int(round(ex.retry_after or 1))))},
            )
        except (ValueError, TypeError) as ex:
            return 400, {"error": structured_error(ex)}, {}
        except Exception as ex:  # pragma: no cover - defensive
            return 500, {"error": structured_error(ex)}, {}

    def _route(
        self,
        method: str,
        path: str,
        payload: Dict[str, Any],
        request_id: Optional[str] = None,
    ) -> Any:
        parts = [p for p in path.split("?", 1)[0].split("/") if p]
        if not parts or parts[0] != "v1":
            raise KeyError(f"unknown path {path}")
        route = parts[1:]
        if route == ["health"] and method == "GET":
            # pre-warm gating: an LB must not route queries here while
            # cached executables are still loading — the state reads
            # "warming" and the daemon answers 503 exactly like a drain
            # (submissions are still ACCEPTED; only readiness is gated)
            ok = self.ready
            state = (
                "warming"
                if self._warming and self._health.healthy
                else self._health.state
            )
            body = {"ok": ok, "state": state}
            if (
                ok
                and state == "healthy"
                and getattr(self._engine, "is_degraded", False)
            ):
                # a device died and the engine rebuilt onto the
                # survivors: still serving (200) but advertising reduced
                # capacity, so the fleet autoscaler treats this replica
                # as sustained pressure (spawn healthy, drain-retire us)
                body["state"] = "degraded"
                body["surviving_devices"] = int(
                    self._engine.surviving_device_count
                )
                body["lost_devices"] = list(self._engine.lost_devices)
            return (200 if ok else 503), body
        if route == ["status"] and method == "GET":
            return 200, self.status()
        if route == ["sessions"]:
            if method == "POST":
                ttl = payload.get("ttl")
                session = self.create_session(
                    ttl=None if ttl is None else float(ttl)
                )
                return 200, {
                    "session_id": session.session_id,
                    "ttl": session.ttl,
                }
            if method == "GET":
                self._sessions.sweep()
                return 200, {"sessions": self._sessions.describe()}
        if len(route) >= 2 and route[0] == "sessions":
            sid = route[1]
            rest = route[2:]
            if not rest and method == "GET":
                return 200, self._sessions.get(sid).describe()
            if (not rest and method == "DELETE") or (
                rest == ["close"] and method == "POST"
            ):
                return 200, self.close_session(sid)
            if rest == ["sql"] and method == "POST":
                return self._route_sql(sid, payload, request_id)
            if rest and rest[0] == "pipelines":
                prest = rest[1:]
                if not prest and method == "POST":
                    return 200, self.register_pipeline(
                        sid, payload,
                        step=bool(payload.get("step", True)),
                    )
                if not prest and method == "GET":
                    return 200, {"pipelines": self.list_pipelines(sid)}
                if len(prest) == 1 and method == "GET":
                    return 200, self.describe_pipeline(sid, prest[0])
                if len(prest) == 1 and method == "DELETE":
                    return 200, self.remove_pipeline(
                        sid, prest[0],
                        drop_table=bool(payload.get("drop_table", False)),
                    )
                if (
                    len(prest) == 2
                    and prest[1] == "step"
                    and method == "POST"
                ):
                    return 200, self.step_pipeline(
                        sid, prest[0],
                        force_refresh=bool(
                            payload.get("force_refresh", False)
                        ),
                    )
        if route == ["admin", "adopt"] and method == "POST":
            state_path = payload.get("state_path")
            if not isinstance(state_path, str) or not state_path.strip():
                raise ValueError(
                    "payload must carry the source replica's 'state_path'"
                )
            return 200, {"adopted": self.adopt_state(state_path)}
        if len(route) >= 2 and route[0] == "jobs":
            jid = route[1]
            rest = route[2:]
            if not rest and method == "GET":
                return 200, self._scheduler.get(jid).snapshot()
            if rest == ["profile"] and method == "GET":
                return 200, self.job_profile(jid)
            if rest == ["cancel"] and method == "POST":
                return 200, self._scheduler.cancel(jid).snapshot(
                    include_result=False
                )
        raise KeyError(f"unknown route {method} {path}")

    def job_profile(self, job_id: str) -> Dict[str, Any]:
        """``GET /v1/jobs/<id>/profile``: the job's per-task runtime
        profile (EXPLAIN ANALYZE). 404 while the job is still running
        or when it was not profiled (submit with ``"profile": true`` or
        set ``fugue.obs.profile`` on the daemon)."""
        job = self._scheduler.get(job_id)
        if job.profile is None:
            raise KeyError(
                f"job {job_id} has no profile (status={job.status}; "
                "submit with 'profile': true, or set fugue.obs.profile "
                "with fugue.obs.enabled on the daemon)"
            )
        return {
            "job_id": job.job_id,
            "session_id": job.session_id,
            "status": job.status,
            "profile": job.profile.as_dict(),
            "text": job.profile.to_text(),
        }

    def explain_sql(self, session_id: str, sql: str) -> Dict[str, Any]:
        """The submission-time ``explain`` flag: compile the FugueSQL
        against the session's hot tables and return the static plan
        report WITHOUT executing anything (classic EXPLAIN). When the
        runtime-statistics store holds history for this query's
        fingerprint, the last observed per-task row counts ride along —
        the replay surface that survives restarts and adoption."""
        session = self._sessions.get(session_id)
        dag = FugueSQLWorkflow()
        dag._sql(sql, {}, **session.table_frames())
        report = dag.explain(engine=self._engine)
        fingerprint = dag.__uuid__()
        out: Dict[str, Any] = {
            "session_id": session_id,
            "fingerprint": fingerprint,
            "explain": {
                "text": report.to_text(),
                "plan": report.to_dict(),
            },
        }
        if self._stats_store is not None:
            latest = self._stats_store.latest(fingerprint)
            if latest is not None:
                out["observed"] = {
                    "recorded_at": latest.get("recorded_at"),
                    "total_ms": latest.get("total_ms"),
                    "rows": self._stats_store.observed_rows(fingerprint),
                    "observations": len(
                        self._stats_store.history(fingerprint)
                    ),
                }
        session.touch()
        return out

    def _route_sql(
        self,
        sid: str,
        payload: Dict[str, Any],
        request_id: Optional[str] = None,
    ) -> Tuple[int, Dict[str, Any]]:
        sql = payload.get("sql")
        if not isinstance(sql, str) or not sql.strip():
            raise ValueError("payload must carry a non-empty 'sql' string")
        if bool(payload.get("explain", False)):
            # EXPLAIN: compile + report, never execute (health-gated
            # like a submission — a draining daemon sheds it)
            self._reject_if_unhealthy()
            return 200, self.explain_sql(sid, sql)
        mode = str(payload.get("mode", "sync")).lower()
        if mode not in ("sync", "async"):
            raise ValueError(f"mode must be sync|async, got {mode!r}")
        degraded = False
        if (
            mode == "sync"
            and self._sync_degrade_depth > 0
            and self._scheduler.backlog() >= self._sync_degrade_depth
        ):
            # under load a sync submit would park an HTTP worker behind
            # a deep queue: degrade to async and hand back the job id
            mode = "async"
            degraded = True
            self._count_reject("sync_degraded")
        # scheduling fields (ISSUE 18): "priority" (int, higher wins;
        # default 0) and "deadline" (relative seconds budget — the job
        # must START within it or it settles with a structured error;
        # 0/absent = none). Converted here to the absolute epoch the
        # scheduler compares against.
        priority = int(payload.get("priority", 0))
        deadline_secs = float(payload.get("deadline", 0.0) or 0.0)
        deadline = time.time() + deadline_secs if deadline_secs > 0 else 0.0
        job = self.submit(
            sid,
            sql,
            save_as=payload.get("save_as"),
            wait=mode == "sync",
            timeout=float(payload.get("timeout", 0.0)),
            collect=bool(payload.get("collect", True)),
            limit=int(payload.get("limit", 10_000)),
            request_id=request_id,
            profile=bool(payload.get("profile", False)),
            priority=priority,
            deadline=deadline,
        )
        if mode == "async":
            snap = job.snapshot(include_result=False)
            if degraded:
                snap["degraded_to_async"] = True
            return 202, snap
        return 200, job.snapshot()
