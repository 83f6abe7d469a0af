"""Conf-key rules: every ``fugue.*`` key in effect is checked against the
declared registry in :mod:`fugue_tpu.constants` — unknown keys get a
did-you-mean suggestion (a typo'd conf key is otherwise SILENTLY ignored
by every engine getter), and values that the typed getters could not
coerce to the declared type are rejected before an engine trips on them
mid-run."""

import difflib
from typing import Any, Iterable

from fugue_tpu.analysis.diagnostics import (
    Diagnostic,
    Rule,
    Severity,
    register_rule,
)
from fugue_tpu.constants import (
    FUGUE_CONF_JAX_DEVICES,
    FUGUE_CONF_JAX_RECOVERY_ENABLED,
    FUGUE_CONF_LAKE_SERVE_PATH,
    FUGUE_CONF_OBS_ENABLED,
    FUGUE_CONF_OBS_PROFILE,
    FUGUE_CONF_OBS_SLOW_QUERY_MS,
    FUGUE_CONF_OBS_TRACE_PATH,
    FUGUE_CONF_SERVE_AUTOSCALE_MAX_REPLICAS,
    FUGUE_CONF_SERVE_FLEET_REPLICAS,
    FUGUE_CONF_SERVE_MAX_CONCURRENT,
    FUGUE_CONF_SERVE_STATE_PATH,
    FUGUE_CONF_STREAM_SOURCE,
    FUGUE_CONF_WORKFLOW_RESUME,
    declared_conf_keys,
)
from fugue_tpu.utils.params import _convert


@register_rule
class UnknownConfKeyRule(Rule):
    code = "FWF201"
    severity = Severity.ERROR
    description = "unknown fugue.* conf key (typo'd keys are silently ignored)"

    def check(self, ctx: Any) -> Iterable[Diagnostic]:
        declared = declared_conf_keys()
        for key in sorted(ctx.conf.keys()):
            if not key.startswith("fugue.") or key in declared:
                continue
            close = difflib.get_close_matches(key, declared.keys(), n=1, cutoff=0.6)
            hint = f" — did you mean '{close[0]}'?" if close else ""
            yield self.diag(
                f"unknown conf key '{key}'{hint} (unknown fugue.* keys are "
                "ignored by every engine)",
            )


@register_rule
class ConfValueTypeRule(Rule):
    code = "FWF202"
    severity = Severity.ERROR
    description = "conf value is not convertible to the key's declared type"

    def check(self, ctx: Any) -> Iterable[Diagnostic]:
        declared = declared_conf_keys()
        for key in sorted(ctx.conf.keys()):
            info = declared.get(key)
            if info is None:
                continue
            value = ctx.conf[key]
            try:
                _convert(value, info.type)
            except Exception:
                yield self.diag(
                    f"conf '{key}' = {value!r} is not convertible to the "
                    f"declared type {info.type.__name__} ({info.description})",
                )


@register_rule
class DaemonResumeOffRule(Rule):
    code = "FWF403"
    severity = Severity.WARN
    description = (
        "daemon-targeted workflow runs with fugue.workflow.resume off: "
        "an interrupted async job re-executes from scratch on failover"
    )

    def check(self, ctx: Any) -> Iterable[Diagnostic]:
        # a durable serve state path in the effective conf marks this as
        # a daemon-targeted run (the daemon's engine conf carries the
        # fugue.serve.* keys it was configured with)
        state_path = str(
            ctx.conf.get(FUGUE_CONF_SERVE_STATE_PATH, "") or ""
        ).strip()
        if state_path == "":
            return
        try:
            # _convert, not bool(): conf values legitimately arrive as
            # strings, and bool("false") is True
            resume = _convert(
                ctx.conf.get(FUGUE_CONF_WORKFLOW_RESUME, False), bool
            )
        except Exception:
            resume = False
        if not resume:
            yield self.diag(
                "the daemon journals interrupted async jobs for restart "
                "recovery, but fugue.workflow.resume is off: a resubmitted "
                "job re-executes every task instead of resuming at its "
                "checkpoint frontier — set fugue.workflow.resume=true (and "
                "a fugue.workflow.checkpoint.path) for cheap failover",
            )


@register_rule
class DaemonColdStartCacheRule(Rule):
    code = "FWF502"
    severity = Severity.WARN
    description = (
        "serve-targeted conf without a persistent executable cache dir: "
        "every daemon restart re-pays full XLA compilation before the "
        "first query"
    )

    def check(self, ctx: Any) -> Iterable[Diagnostic]:
        state_path = str(
            ctx.conf.get(FUGUE_CONF_SERVE_STATE_PATH, "") or ""
        ).strip()
        if state_path == "":
            return
        # the SAME resolution run() and the engine use (new key, then
        # the deprecated fugue.jax.compile.cache alias + env var), so
        # the gate and the engine can never disagree about whether the
        # disk tier is on
        from fugue_tpu.optimize.exec_cache import resolve_cache_dir

        if resolve_cache_dir(ctx.conf) != "":
            return
        yield self.diag(
            "the daemon journals sessions and jobs for restart recovery "
            "(fugue.serve.state_path is set), but no persistent "
            "executable cache dir is configured: a restarted daemon "
            "re-pays the full XLA compile of every hot query before its "
            "first answer — set fugue.optimize.cache.dir so restarts "
            "pre-warm from disk and time_to_first_query stays IO-bound",
        )


@register_rule
class ServeConcurrencyDispatchLockRule(Rule):
    code = "FWF503"
    severity = Severity.WARN
    description = (
        "serve-targeted conf with concurrent submissions but an engine "
        "whose task_execution_lock is None: concurrent device dispatch "
        "of collective programs can deadlock (XLA CPU rendezvous)"
    )

    def check(self, ctx: Any) -> Iterable[Diagnostic]:
        # only a conf that EXPLICITLY carries the serve concurrency key
        # is serve-targeted; and with no live engine the lock is unknowable
        if FUGUE_CONF_SERVE_MAX_CONCURRENT not in ctx.conf or ctx.engine is None:
            return
        try:
            max_concurrent = _convert(
                ctx.conf[FUGUE_CONF_SERVE_MAX_CONCURRENT], int
            )
        except Exception:
            return  # FWF202 already rejects the unconvertible value
        if max_concurrent <= 1:
            return
        if getattr(ctx.engine, "task_execution_lock", None) is None:
            yield self.diag(
                f"fugue.serve.max_concurrent={max_concurrent} but the "
                "target engine's task_execution_lock is None: two "
                "concurrently dispatched programs with collectives can "
                "starve each other's rendezvous participants and "
                "deadlock (the PR 6 shared-engine hazard) — serve "
                "through an engine that serializes task execution, or "
                "set fugue.serve.max_concurrent=1",
            )


@register_rule
class FleetSharedStateRule(Rule):
    code = "FWF504"
    severity = Severity.WARN
    description = (
        "fleet conf with replicas > 1 but no shared serve state path "
        "or no shared executable cache dir: failover and cross-replica "
        "warm starts silently degrade"
    )

    def check(self, ctx: Any) -> Iterable[Diagnostic]:
        if FUGUE_CONF_SERVE_FLEET_REPLICAS not in ctx.conf:
            return
        raw = ctx.conf[FUGUE_CONF_SERVE_FLEET_REPLICAS]
        try:
            replicas = _convert(raw, int)
        except Exception:
            return  # FWF202 already rejects the unconvertible value
        if replicas <= 1:
            return
        state_path = str(
            ctx.conf.get(FUGUE_CONF_SERVE_STATE_PATH, "") or ""
        ).strip()
        if state_path == "":
            yield self.diag(
                f"fugue.serve.fleet.replicas={replicas} but no shared "
                "fugue.serve.state_path: the per-replica journals under "
                "it are what a survivor adopts on replica death or a "
                "rolling-restart drain — without one, failover has "
                "nothing to migrate and every session dies with its "
                "replica",
            )
        # the SAME resolution run() and the engine use (new key, then
        # the deprecated alias), so this gate and FWF502 cannot drift
        from fugue_tpu.optimize.exec_cache import resolve_cache_dir

        if resolve_cache_dir(ctx.conf) == "":
            yield self.diag(
                f"fugue.serve.fleet.replicas={replicas} but no shared "
                "fugue.optimize.cache.dir: every replica (and every "
                "rolling-restart fresh daemon) re-pays full XLA "
                "compilation instead of warm-starting from the fleet's "
                "shared executable cache",
            )


@register_rule
class ObsDependentConfWithoutObsRule(Rule):
    code = "FWF505"
    severity = Severity.WARN
    description = (
        "fugue.obs.slow_query_ms or fugue.obs.profile is set but "
        "fugue.obs.enabled is off: the conf is silently inert"
    )

    def check(self, ctx: Any) -> Iterable[Diagnostic]:
        try:
            # _convert, not bool(): conf values legitimately arrive as
            # strings, and bool("false") is True (FWF404's idiom)
            enabled = _convert(
                ctx.conf.get(FUGUE_CONF_OBS_ENABLED, False), bool
            )
        except Exception:
            enabled = False
        if enabled:
            return
        try:
            slow_ms = float(
                ctx.conf.get(FUGUE_CONF_OBS_SLOW_QUERY_MS, 0.0) or 0.0
            )
        except Exception:
            slow_ms = 0.0
        if slow_ms > 0:
            yield self.diag(
                f"fugue.obs.slow_query_ms={slow_ms:g} but fugue.obs.enabled "
                "is off: embedded runs never open a trace, so no slow-query "
                "record (or span breakdown) is ever produced — set "
                "fugue.obs.enabled=true (or drop the threshold)",
            )
        try:
            profile = _convert(
                ctx.conf.get(FUGUE_CONF_OBS_PROFILE, False), bool
            )
        except Exception:
            profile = False
        if profile:
            yield self.diag(
                "fugue.obs.profile is on but fugue.obs.enabled is off: the "
                "profiler's conf gate needs the span tracer for the "
                "compile/execute/transfer split, so runs are NOT profiled "
                "and FugueWorkflowResult.profile() stays None — set "
                "fugue.obs.enabled=true (the serving 'profile' submission "
                "flag forces profiling per request instead)",
            )


@register_rule
class StreamConfRule(Rule):
    code = "FWF506"
    severity = Severity.WARN
    description = (
        "fugue.stream.* keys set without a streaming source (inert), or "
        "a standing pipeline without fugue.workflow.resume (a restart "
        "refolds every consumed file from scratch)"
    )

    def check(self, ctx: Any) -> Iterable[Diagnostic]:
        stream_keys = sorted(
            k for k in ctx.conf.keys() if k.startswith("fugue.stream.")
        )
        if not stream_keys:
            return
        source = str(
            ctx.conf.get(FUGUE_CONF_STREAM_SOURCE, "") or ""
        ).strip()
        if source == "":
            for key in stream_keys:
                if key == FUGUE_CONF_STREAM_SOURCE:
                    continue
                yield self.diag(
                    f"'{key}' is set but {FUGUE_CONF_STREAM_SOURCE} is "
                    "empty: no standing pipeline tails anything, so the "
                    "key is silently inert — set the source dir/URI (or "
                    "drop the fugue.stream.* keys)",
                )
            return
        try:
            # _convert, not bool(): conf values legitimately arrive as
            # strings, and bool("false") is True (FWF404's idiom)
            resume = _convert(
                ctx.conf.get(FUGUE_CONF_WORKFLOW_RESUME, False), bool
            )
        except Exception:
            resume = False
        if not resume:
            yield self.diag(
                f"{FUGUE_CONF_STREAM_SOURCE} configures a standing "
                "pipeline but fugue.workflow.resume is off: the pipeline "
                "keeps no durable progress manifest, so a killed driver "
                "restarts from scratch and refolds every consumed file "
                "(double-counted aggregates if the view was already "
                "published) — set fugue.workflow.resume=true for "
                "exactly-once restart",
            )


@register_rule
class LakeConfRule(Rule):
    code = "FWF507"
    severity = Severity.WARN
    description = (
        "fugue.lake.* keys set but nothing reads or writes a lake:// "
        "table (inert), or AS OF time travel against a non-lake path"
    )

    def check(self, ctx: Any) -> Iterable[Diagnostic]:
        from fugue_tpu.extensions import builtins as _b
        from fugue_tpu.lake.format import is_lake_uri

        def _task_path(t: Any) -> Any:
            p = t.params.get("path", None)
            if isinstance(p, (list, tuple)):
                p = p[0] if p else None
            return p if isinstance(p, str) else None

        touches_lake = False
        for t in ctx.tasks:
            if t.extension not in (_b.Load, _b.Save):
                continue
            path = _task_path(t)
            if path is not None and is_lake_uri(path):
                touches_lake = True
            if t.extension is _b.Load:
                params = dict(t.params.get("params", None) or {})
                pinned = [
                    k for k in ("version", "timestamp") if k in params
                ]
                if pinned and path is not None and not is_lake_uri(path):
                    yield self.diag(
                        f"AS OF ({'/'.join(pinned)}) on load of "
                        f"'{path}': time travel only applies to lake:// "
                        "tables — a plain file path has no snapshot "
                        "history, so this load fails at run time "
                        "(prefix the path with lake:// or drop AS OF)",
                        t,
                    )
        lake_keys = sorted(
            k for k in ctx.conf.keys() if k.startswith("fugue.lake.")
        )
        if not lake_keys:
            return
        # fugue.lake.serve.path anchors lake usage by itself: it turns
        # on the serve sessions' lake-backed durable tables, which no
        # workflow task would reveal
        serve_path = str(
            ctx.conf.get(FUGUE_CONF_LAKE_SERVE_PATH, "") or ""
        ).strip()
        if serve_path != "" or touches_lake:
            return
        for key in lake_keys:
            yield self.diag(
                f"'{key}' is set but no task loads or saves a lake:// "
                "table and fugue.lake.serve.path is empty: the key is "
                "silently inert — point a LOAD/SAVE at a lake:// URI "
                "(or drop the fugue.lake.* keys)",
            )


@register_rule
class AutoscaleConfRule(Rule):
    code = "FWF508"
    severity = Severity.WARN
    description = (
        "fugue.serve.autoscale.* keys set without an elastic fleet "
        "(inert), or autoscaling without a shared serve state path "
        "(scale-down drains have no journal for the survivor to adopt)"
    )

    def check(self, ctx: Any) -> Iterable[Diagnostic]:
        autoscale_keys = sorted(
            k for k in ctx.conf.keys()
            if k.startswith("fugue.serve.autoscale.")
        )
        if not autoscale_keys:
            return
        try:
            max_replicas = _convert(
                ctx.conf.get(FUGUE_CONF_SERVE_AUTOSCALE_MAX_REPLICAS, 0), int
            )
        except Exception:
            return  # FWF202 already rejects the unconvertible value
        if max_replicas <= 0:
            # the master switch is off (or absent): every other
            # autoscale key is silently inert
            for key in autoscale_keys:
                if key == FUGUE_CONF_SERVE_AUTOSCALE_MAX_REPLICAS:
                    continue
                yield self.diag(
                    f"'{key}' is set but "
                    f"{FUGUE_CONF_SERVE_AUTOSCALE_MAX_REPLICAS} is unset "
                    "(or <= 0): no autoscaler is ever constructed, so the "
                    "key is silently inert — set a positive max_replicas "
                    "(or drop the fugue.serve.autoscale.* keys)",
                )
            return
        if FUGUE_CONF_SERVE_FLEET_REPLICAS not in ctx.conf:
            yield self.diag(
                f"{FUGUE_CONF_SERVE_AUTOSCALE_MAX_REPLICAS}="
                f"{max_replicas} but {FUGUE_CONF_SERVE_FLEET_REPLICAS} "
                "is absent: the autoscaler only runs inside a ServeFleet, "
                "and this conf never constructs one — an embedded daemon "
                "ignores every fugue.serve.autoscale.* key (set "
                "fugue.serve.fleet.replicas, or drop the autoscale keys)",
            )
        state_path = str(
            ctx.conf.get(FUGUE_CONF_SERVE_STATE_PATH, "") or ""
        ).strip()
        if state_path == "":
            yield self.diag(
                f"{FUGUE_CONF_SERVE_AUTOSCALE_MAX_REPLICAS}="
                f"{max_replicas} but no shared fugue.serve.state_path: "
                "scale-down drains a replica's sessions to its journal "
                "for a survivor to adopt — without one there is nothing "
                "to adopt, so every autoscale retire loses the sessions "
                "it drains",
            )


@register_rule
class DeviceRecoveryConfRule(Rule):
    code = "FWF509"
    severity = Severity.WARN
    description = (
        "fugue.jax.recovery.* keys with a single-device mesh (recovery "
        "is inert: losing the only device leaves no survivors), or "
        "recovery enabled without a resumable checkpoint/lake lineage "
        "path (mid-flight frames fail their query on device loss)"
    )

    def check(self, ctx: Any) -> Iterable[Diagnostic]:
        recovery_keys = sorted(
            k for k in ctx.conf.keys()
            if k.startswith("fugue.jax.recovery.")
        )
        if not recovery_keys:
            return
        # single-device pin: degraded-mesh rebuild needs at least one
        # SURVIVOR, so a one-device mesh can never recover from a loss
        devices = str(ctx.conf.get(FUGUE_CONF_JAX_DEVICES, "") or "").strip()
        pinned = [p for p in devices.split(",") if p.strip() != ""]
        if len(pinned) == 1:
            for key in recovery_keys:
                yield self.diag(
                    f"'{key}' is set but {FUGUE_CONF_JAX_DEVICES}="
                    f"'{devices}' pins the mesh to a single device: "
                    "degraded-mesh recovery rebuilds onto the SURVIVORS "
                    "of a loss, and a one-device mesh has none — the key "
                    "is silently inert (widen the device slice or drop "
                    "the fugue.jax.recovery.* keys)",
                )
            return
        try:
            # _convert, not bool(): conf values legitimately arrive as
            # strings, and bool("false") is True
            enabled = _convert(
                ctx.conf.get(FUGUE_CONF_JAX_RECOVERY_ENABLED, True), bool
            )
        except Exception:
            enabled = True
        if not enabled:
            return
        try:
            resume = _convert(
                ctx.conf.get(FUGUE_CONF_WORKFLOW_RESUME, False), bool
            )
        except Exception:
            resume = False
        if resume:
            return
        # a PINNED lake load is deterministic lineage: recovery can
        # re-read the exact snapshot onto the degraded mesh
        from fugue_tpu.extensions import builtins as _b
        from fugue_tpu.lake.format import is_lake_uri, parse_lake_uri

        for t in ctx.tasks:
            if t.extension is not _b.Load:
                continue
            p = t.params.get("path", None)
            if isinstance(p, (list, tuple)):
                p = p[0] if p else None
            if not isinstance(p, str) or not is_lake_uri(p):
                continue
            params = dict(t.params.get("params", None) or {})
            try:
                _, uri_params = parse_lake_uri(p)
            except Exception:
                uri_params = {}
            if (
                "version" in params or "timestamp" in params
                or "version" in uri_params or "timestamp" in uri_params
            ):
                return  # pinned lake lineage: rematerializable
        yield self.diag(
            f"{FUGUE_CONF_JAX_RECOVERY_ENABLED} is on but the workflow "
            "has no resumable lineage path: no checkpointing "
            "(fugue.workflow.resume is off) and no pinned lake:// AS OF "
            "load — on device loss, frames whose shards cannot be "
            "evacuated have nothing durable to re-materialize from, so "
            "their owning query fails with DeviceLostError instead of "
            "recovering — set fugue.workflow.resume=true (with a "
            "checkpoint path) or pin lake reads to a version/timestamp",
        )


@register_rule
class ObsTracePathWithoutObsRule(Rule):
    code = "FWF404"
    severity = Severity.WARN
    description = (
        "fugue.obs.trace_path is set but fugue.obs.enabled is off: "
        "no trace file will ever be written"
    )

    def check(self, ctx: Any) -> Iterable[Diagnostic]:
        trace_path = str(
            ctx.conf.get(FUGUE_CONF_OBS_TRACE_PATH, "") or ""
        ).strip()
        if trace_path == "":
            return
        try:
            # _convert, not bool(): conf values legitimately arrive as
            # strings, and bool("false") is True
            enabled = _convert(
                ctx.conf.get(FUGUE_CONF_OBS_ENABLED, False), bool
            )
        except Exception:
            enabled = False
        if not enabled:
            yield self.diag(
                f"fugue.obs.trace_path is set to '{trace_path}' but "
                "fugue.obs.enabled is off: no trace is ever opened, so "
                "no trace file will be written there — set "
                "fugue.obs.enabled=true to get per-run Chrome-trace "
                "JSON (or drop the trace_path)",
            )
