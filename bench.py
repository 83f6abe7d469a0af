"""Benchmark: the BASELINE.md headline plus all five BASELINE configs.

Prints ONE json line (driver contract):
``{"metric":..., "value":..., "unit":..., "vs_baseline":..., "detail":...}``
where value is the jax engine's rows/sec on the 100M-row numeric
transform()+groupby and ``vs_baseline`` its speedup over native. The
``detail.configs`` dict carries every BASELINE.md config (1-5), each with
native/jax secs + rows/sec + speedup. Set ``BENCH_CONFIGS=lines`` to also
print one json line per config (for humans; the driver reads line 1).
The SAME headline line is printed again LAST: the driver stores only the
output tail, so the artifact stays self-contained.

Env knobs: BENCH_ROWS (default 100_000_000), BENCH_GROUPS (1024),
BENCH_NATIVE_ROWS (10_000_000), BENCH_SMALL=1 (scale everything down ~100x
for a fast smoke run).
"""

import json
import os
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, Tuple

_SMALL = os.environ.get("BENCH_SMALL", "") in ("1", "true")
_CHECKOUT = os.path.dirname(os.path.abspath(__file__))

# pinned native denominators (rows/sec), measured 2026-07-30 on this
# round's container (config 4 re-pinned the same day
# when its workload moved to the user-level zip+transform path). The
# LIVE native run keeps feeding vs_baseline — vs_baseline_pinned divides
# by these so round-over-round numbers stop tracking the ambient
# variance of the native rerun.
_PINNED_NATIVE_RPS = {
    "headline": 24_973_678.0,
    "1_map_letter_to_food": 26_600_151.0,
    "2_partition_udf": 3_118_399.0,
    "3_fuguesql_groupby": 33_436_836.0,
    "3b_sql_join": 12_610_482.0,
    "4_cotransform": 9_335.0,
    "5_e2e_parquet": 23_835_434.0,
}


def _scale(n: int) -> int:
    return max(10_000, n // 100) if _SMALL else n


def _timed(fn: Callable[[], Any], warm: int = 5) -> float:
    """Best of `warm` runs after a cold run (the first call compiles; the
    minimum of the warm runs is the statistic every config reports)."""
    fn()  # cold
    samples = []
    for _ in range(warm):
        t0 = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - t0)
    return min(samples)


# HBM peak bandwidth by TPU generation (GB/s) — the roofline denominator.
# Sources: published TPU system specs (v5e 819, v5p 2765, v4 1228,
# v6e/Trillium 1640). Matched against device_kind fragments; "v5 lite"
# comes before "v5" so v5e doesn't read as v5p.
_HBM_PEAK_GBPS = (
    ("v5 lite", 819.0),
    ("v5e", 819.0),
    ("v5p", 2765.0),
    ("v5", 2765.0),
    ("v6", 1640.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
)


def _platform_peak_gbps(dev: Any) -> Any:
    if dev.platform == "cpu":
        return None
    kind = str(getattr(dev, "device_kind", "")).lower()
    for frag, peak in _HBM_PEAK_GBPS:
        if frag in kind:
            return peak
    return None


def _roofline(
    build_result_frame: Callable[[], Any],
    bytes_touched: int,
    engine: Any = None,
) -> Dict[str, Any]:
    """Decompose a device pipeline's cost: measure the irreducible
    sync+fetch latency of one scalar readback with a tiny op, then the
    full pipeline ending in ONE derived-scalar fetch (which forces all
    queued compute through the same single sync). The
    difference is the device-resident time; bytes_touched / that time is
    a LOWER bound on achieved HBM bandwidth (bytes_touched counts each
    logical pass over the data once; XLA fusion can only reduce real
    traffic below it). Achieved GB/s is also reported as a % of the
    platform's HBM peak, and — when ``engine`` is passed — against XLA's
    OWN traffic accounting (``jit(...).lower().compile().cost_analysis()``
    of the engine programs that ran), which proves or disproves whether
    the compiler's real traffic is near the logical bound."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fugue_tpu.jax_backend.blocks import residency_arrays

    # the sync baseline must live on the SAME backend as the pipeline
    # (frames may sit on the host CPU-XLA tier, where a sync is ~free)
    probe = build_result_frame()
    blocks0 = getattr(probe, "native", None)
    if blocks0 is None or not hasattr(blocks0, "mesh") or not any(
        c.on_device for c in blocks0.columns.values()
    ):
        return {"skipped": "result frame not device-resident (fallback?)"}
    dev = blocks0.mesh.devices.flat[0]
    tiny = jax.device_put(jnp.ones((8,), jnp.float32), dev)
    jax.block_until_ready(tiny)

    def rtt_once() -> float:
        t0 = time.perf_counter()
        float(jnp.sum(tiny * np.float32(np.random.rand())))
        return time.perf_counter() - t0

    rtt_once()
    rtt = min(rtt_once() for _ in range(5))

    if engine is not None:
        # scope cost_analysis to exactly the programs this pipeline runs
        engine.reset_program_log()

    def dev_once() -> float:
        t0 = time.perf_counter()
        fr = build_result_frame()
        parts = [
            jnp.sum(a.astype(jnp.float32))
            for a in residency_arrays(fr.native)
        ]
        float(jnp.sum(jnp.stack(parts)))  # one sync drains the pipeline
        return time.perf_counter() - t0

    dev_once()  # warm (possible jit of the reduction)
    dev_plus = min(dev_once() for _ in range(5))
    device_secs = max(dev_plus - rtt, 0.0)
    peak = _platform_peak_gbps(dev)
    gbps = (
        None
        if device_secs <= 0
        else round(bytes_touched / device_secs / 1e9, 1)
    )
    out: Dict[str, Any] = {
        "backend": dev.platform,
        "sync_rtt_secs": round(rtt, 4),
        "device_plus_rtt_secs": round(dev_plus, 4),
        "device_resident_secs": round(device_secs, 4),
        "approx_bytes_touched": bytes_touched,
        "achieved_gbps_lower_bound": gbps,
        "platform_peak_gbps": peak,
        "pct_of_peak_lower_bound": (
            None
            if gbps is None or not peak
            else round(100.0 * gbps / peak, 2)
        ),
    }
    if engine is not None:
        try:
            ca = engine.program_cost_analysis()
        except Exception:  # pragma: no cover - analysis unsupported
            ca = {"flops": 0.0, "bytes_accessed": 0.0, "programs": {}}
        if ca.get("bytes_accessed"):
            xla_gbps = (
                None
                if device_secs <= 0
                else round(ca["bytes_accessed"] / device_secs / 1e9, 1)
            )
            out["xla_cost_analysis"] = {
                "flops": ca["flops"],
                "bytes_accessed": ca["bytes_accessed"],
                "programs": {
                    k: {
                        "flops": v["flops"],
                        "bytes_accessed": v["bytes_accessed"],
                    }
                    for k, v in ca["programs"].items()
                },
                "achieved_gbps_xla": xla_gbps,
                "pct_of_peak_xla": (
                    None
                    if xla_gbps is None or not peak
                    else round(100.0 * xla_gbps / peak, 2)
                ),
                # >1 means XLA's real traffic exceeds the logical
                # bytes-touched bound (e.g. a materialized one-hot): the
                # "bandwidth gap" is then compiler traffic, not an idle
                # memory system — the cost_analysis()-based proof ISSUE
                # r6 asks for when the lower bound can't be raised
                "traffic_ratio_xla_vs_logical": (
                    None
                    if not bytes_touched
                    else round(ca["bytes_accessed"] / bytes_touched, 2)
                ),
            }
    return out


def _pair(
    rows: int,
    native_fn: Callable,
    jax_fn: Callable,
    pinned_key: str = "",
) -> Dict[str, Any]:
    native_secs = _timed(native_fn)
    jax_secs = _timed(jax_fn)
    out = {
        "rows": rows,
        "native_secs": round(native_secs, 4),
        "jax_secs": round(jax_secs, 4),
        "native_rows_per_sec": round(rows / native_secs, 1),
        "jax_rows_per_sec": round(rows / jax_secs, 1),
        "speedup": round(native_secs / jax_secs, 2),
    }
    pinned = _PINNED_NATIVE_RPS.get(pinned_key)
    if pinned and not _SMALL:
        out["speedup_pinned"] = round((rows / jax_secs) / pinned, 2)
    return out


def _governance_overhead(
    pdf: Any, jax_udf: Callable, n_rows: int
) -> Dict[str, Any]:
    """Memory-governance overhead block (ISSUE r9): the SAME
    transform+groupby pipeline on a governed engine (generous
    budget_fraction — ledger + admission active, zero spills expected)
    vs a fresh ungoverned engine, plus the governed run's peak ledger
    bytes per tier and spill count. The governed headline must stay
    within noise of the ungoverned one — a regression here means the
    ledger/admission layer leaked onto the hot path."""
    import jax

    from fugue_tpu import transform
    from fugue_tpu.column import col
    from fugue_tpu.column import functions as ff
    from fugue_tpu.execution import make_execution_engine
    from fugue_tpu.execution.api import aggregate

    def run_on(eng: Any) -> float:
        src = eng.persist(eng.to_df(pdf))

        def once() -> None:
            out = transform(
                src, jax_udf, schema="k:int,v2:float", engine=eng,
                as_fugue=True,
            )
            agg = aggregate(
                out, partition_by="k",
                s=ff.sum(col("v2")), m=ff.avg(col("v2")),
                c=ff.count(col("v2")),
                engine=eng, as_fugue=True,
            )
            arrs = [
                c_.data for c_ in agg.native.columns.values() if c_.on_device
            ]
            if agg.native.row_valid is not None:  # type: ignore
                arrs.append(agg.native.row_valid)  # type: ignore
            jax.device_get(arrs)

        return _timed(once, warm=3)

    ungoverned = make_execution_engine("jax")
    governed = make_execution_engine(
        "jax", {"fugue.jax.memory.budget_fraction": 0.8}
    )
    ungoverned_secs = run_on(ungoverned)
    governed_secs = run_on(governed)
    stats = governed.memory_stats
    ratio = governed_secs / max(ungoverned_secs, 1e-9)
    within_noise = ratio < 1.15
    if not within_noise:
        import sys

        print(
            f"WARNING: governed run {ratio:.2f}x the ungoverned run "
            "(> 1.15 noise band) — memory governance overhead regressed",
            file=sys.stderr,
        )
    return {
        "rows": n_rows,
        "governed_secs": round(governed_secs, 4),
        "ungoverned_secs": round(ungoverned_secs, 4),
        "overhead_ratio": round(ratio, 3),
        "within_noise": within_noise,
        "budget_bytes": stats["budget_bytes"],
        "peak_bytes": dict(stats["peak"]),
        "spills": stats["counters"]["spills"],
        "pressure_events": stats["counters"]["pressure_events"],
        "admissions": {
            "device": stats["counters"]["admissions_device"],
            "host": stats["counters"]["admissions_host"],
        },
    }


def _observability_overhead(
    pdf: Any, jax_udf: Callable, n_rows: int
) -> Dict[str, Any]:
    """Observability overhead block (ISSUE 8): the SAME workflow
    pipeline (transform + partitioned aggregate through
    ``FugueWorkflow.run``, which is where the span instrumentation
    lives) on an obs-ON engine (tracing enabled, per-run Chrome-trace
    export to ``memory://``) vs an obs-OFF engine. The obs-on run must
    stay within 1.05x of obs-off — a regression here means span/metric
    instrumentation leaked onto the hot path."""
    from fugue_tpu.column import col
    from fugue_tpu.column import functions as ff
    from fugue_tpu.execution import make_execution_engine
    from fugue_tpu.workflow.workflow import FugueWorkflow

    rows = min(int(n_rows), 2_000_000)  # per-iteration ingest: bound it
    sub = pdf.iloc[:rows]

    def run_on(eng: Any) -> float:
        def once() -> None:
            dag = FugueWorkflow()
            df = dag.df(sub)
            out = df.transform(jax_udf, schema="k:int,v2:float")
            agg = out.partition_by("k").aggregate(
                s=ff.sum(col("v2")), m=ff.avg(col("v2")),
                c=ff.count(col("v2")),
            )
            agg.yield_dataframe_as("res", as_local=True)
            dag.run(eng)["res"].as_array()

        return _timed(once, warm=3)

    obs_off = make_execution_engine("jax")
    obs_on = make_execution_engine(
        "jax",
        {
            "fugue.obs.enabled": True,
            "fugue.obs.trace_path": "memory://bench_obs_traces",
        },
    )
    obs_off_secs = run_on(obs_off)
    obs_on_secs = run_on(obs_on)
    ratio = obs_on_secs / max(obs_off_secs, 1e-9)
    within_noise = ratio <= 1.05
    if not within_noise:
        import sys

        print(
            f"WARNING: obs-on run {ratio:.2f}x the obs-off run "
            "(> 1.05 band) — observability overhead regressed",
            file=sys.stderr,
        )
    snap = obs_on.metrics.snapshot()
    exported = sum(
        s["value"]
        for s in (
            snap.get("fugue_obs_traces_exported_total", {}).get("samples")
            or []
        )
    )
    return {
        "rows": rows,
        "obs_on_secs": round(obs_on_secs, 4),
        "obs_off_secs": round(obs_off_secs, 4),
        "overhead_ratio": round(ratio, 3),
        "within_noise": within_noise,
        "traces_exported": int(exported),
        "compile_cache": obs_on.compile_cache_stats,
    }


def _profiler_overhead(
    pdf: Any, jax_udf: Callable, n_rows: int
) -> Dict[str, Any]:
    """Profiler overhead block (ISSUE 14): the SAME workflow pipeline as
    ``detail.observability`` with the per-task profiler ON
    (``fugue.obs.profile`` + ``fugue.obs.enabled``) vs everything OFF.
    The profiled run must stay within 1.05x — the profiler's per-task
    row counts, byte estimates and counter sampling live at task
    granularity, not per row, so the bar is the same as obs alone."""
    from fugue_tpu.column import col
    from fugue_tpu.column import functions as ff
    from fugue_tpu.execution import make_execution_engine
    from fugue_tpu.workflow.workflow import FugueWorkflow

    rows = min(int(n_rows), 2_000_000)  # per-iteration ingest: bound it
    sub = pdf.iloc[:rows]
    last_profile: Dict[str, Any] = {}

    def run_on(eng: Any, capture: bool = False) -> float:
        def once() -> None:
            dag = FugueWorkflow()
            df = dag.df(sub)
            out = df.transform(jax_udf, schema="k:int,v2:float")
            agg = out.partition_by("k").aggregate(
                s=ff.sum(col("v2")), m=ff.avg(col("v2")),
                c=ff.count(col("v2")),
            )
            agg.yield_dataframe_as("res", as_local=True)
            res = dag.run(eng)
            res["res"].as_array()
            if capture:
                prof = res.profile()
                if prof is not None:
                    last_profile["tasks"] = len(prof.records)
                    last_profile["top"] = prof.top_tasks(1)

        return _timed(once, warm=3)

    prof_off = make_execution_engine("jax")
    prof_on = make_execution_engine(
        "jax",
        {"fugue.obs.enabled": True, "fugue.obs.profile": True},
    )
    off_secs = run_on(prof_off)
    on_secs = run_on(prof_on, capture=True)
    ratio = on_secs / max(off_secs, 1e-9)
    within_noise = ratio <= 1.05
    if not within_noise:
        import sys

        print(
            f"WARNING: profiler-on run {ratio:.2f}x the profiler-off run "
            "(> 1.05 band) — per-task profiler overhead regressed",
            file=sys.stderr,
        )
    return {
        "rows": rows,
        "profile_on_secs": round(on_secs, 4),
        "profile_off_secs": round(off_secs, 4),
        "overhead_ratio": round(ratio, 3),
        "within_noise": within_noise,
        "tasks_profiled": last_profile.get("tasks", 0),
        "top_task": (last_profile.get("top") or [{}])[0],
    }


def _optimizer_pipeline_bench(n: int, warm: int = 3) -> Dict[str, Any]:
    """ISSUE 10: narrow-consumer e2e parquet pipeline, optimizer on vs
    off. The WIDE file (8 columns) feeds load -> filter -> select(k, v)
    -> SQL groupby through the WORKFLOW layer (the optimizer rewrites
    the DAG; direct engine-API calls bypass it). With ``fugue.optimize``
    on, projection pushdown threads the 2-column requirement through the
    filter into the streamed ingest's narrow-load planner, so the 6 pad
    columns are never decoded or staged; off, the filter materializes
    the full 8-column frame first. The acceptance bar is on/off > 1.2x."""
    import numpy as np
    import pandas as pd

    from fugue_tpu.column import col
    from fugue_tpu.execution import make_execution_engine
    from fugue_tpu.optimize import get_plan_cache
    from fugue_tpu.workflow.workflow import FugueWorkflow

    rng = np.random.default_rng(17)
    tmp = tempfile.mkdtemp(prefix="fugue_bench_opt_")
    src = os.path.join(tmp, "wide.parquet")
    wide = pd.DataFrame(
        {
            "k": rng.integers(0, 256, n).astype(np.int64),
            "v": rng.random(n),
        }
    )
    for i in range(6):
        wide[f"pad{i}"] = rng.random(n)
    wide.to_parquet(src, row_group_size=max(n // 32, 10_000))

    io_conf = {"fugue.jax.io.batch_rows": max(n // 8, 65_536)}
    engines = {
        mode: make_execution_engine(
            "jax", {**io_conf, "fugue.optimize": mode}
        )
        for mode in ("off", "on")
    }

    def run(mode: str) -> None:
        dag = FugueWorkflow()
        df = dag.load(src).filter(col("k") < 128).select("k", "v")
        dag.select(
            "SELECT k, SUM(v) AS s, COUNT(*) AS c FROM", df, "GROUP BY k"
        ).yield_dataframe_as("out", as_local=True)
        dag.run(engines[mode])

    off_secs = _timed(lambda: run("off"), warm=warm)
    on_secs = _timed(lambda: run("on"), warm=warm)
    speedup = round(off_secs / max(on_secs, 1e-9), 2)
    if speedup < 1.2:
        import sys

        print(
            f"WARNING: optimizer-on narrow-consumer pipeline only "
            f"{speedup:.2f}x optimizer-off (acceptance bar is 1.2x)",
            file=sys.stderr,
        )
    return {
        "rows": n,
        "columns_total": 8,
        "columns_consumed": 2,
        "narrow_off_secs": round(off_secs, 4),
        "narrow_on_secs": round(on_secs, 4),
        "narrow_speedup": speedup,
        "plan_cache": get_plan_cache().stats(),
    }


def _bench_headline() -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pandas as pd

    from fugue_tpu import transform
    from fugue_tpu.column import col
    from fugue_tpu.column import functions as ff
    from fugue_tpu.execution import make_execution_engine
    from fugue_tpu.execution.api import aggregate

    n_rows = _scale(int(os.environ.get("BENCH_ROWS", 100_000_000)))
    n_groups = int(os.environ.get("BENCH_GROUPS", 1024))
    n_native = min(
        n_rows, _scale(int(os.environ.get("BENCH_NATIVE_ROWS", 10_000_000)))
    )

    rng = np.random.default_rng(42)
    # float32 + int32: TPU-friendly dtypes (f64 has no TPU hardware path)
    keys = rng.integers(0, n_groups, n_rows).astype(np.int32)
    values = rng.random(n_rows).astype(np.float32)

    # ---- native (pandas) baseline ---------------------------------------
    pdf_small = pd.DataFrame({"k": keys[:n_native], "v": values[:n_native]})

    def pandas_udf(df: pd.DataFrame) -> pd.DataFrame:
        return df.assign(v2=df["v"] * 2.0 + 1.0)

    native = make_execution_engine("native")

    def run_native() -> None:
        out = transform(pdf_small, pandas_udf, schema="*,v2:float",
                        engine=native, as_fugue=True)
        agg = aggregate(
            out, partition_by="k",
            s=ff.sum(col("v2")), m=ff.avg(col("v2")), c=ff.count(col("v2")),
            engine=native, as_fugue=True,
        )
        agg.as_local()

    native_samples = []
    for _ in range(2):
        t0 = time.perf_counter()
        run_native()
        native_samples.append(time.perf_counter() - t0)
    native_secs = min(native_samples)  # same statistic as the jax side
    native_rps = n_native / native_secs

    # ---- jax engine (device) --------------------------------------------
    jdf_pd = pd.DataFrame({"k": keys, "v": values})
    engine = make_execution_engine("jax")

    def jax_udf(arrs: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        return {"k": arrs["k"], "v2": arrs["v"] * jnp.float32(2.0) + 1.0}

    # device placement outside the timed region, matching the reference
    # measurement shape (data already in the engine): persist forces the
    # lazy ingest NOW so jax_cold_secs measures trace+compile (a cache hit
    # when fugue.jax.compile.cache is warm), not the one-time staging of
    # 800MB over the host->device link
    src = engine.persist(engine.to_df(jdf_pd))

    def run_once() -> float:
        t0 = time.perf_counter()
        out = transform(src, jax_udf, schema="k:int,v2:float", engine=engine,
                        as_fugue=True)
        agg = aggregate(
            out, partition_by="k",
            s=ff.sum(col("v2")), m=ff.avg(col("v2")), c=ff.count(col("v2")),
            engine=engine, as_fugue=True,
        )
        # materialize the (small) result to host — the honest endpoint,
        # same as the native path's as_local(). One async wave.
        arrs = [c.data for c in agg.native.columns.values() if c.on_device]
        if agg.native.row_valid is not None:  # type: ignore
            arrs.append(agg.native.row_valid)  # type: ignore
        jax.device_get(arrs)
        return time.perf_counter() - t0

    cold_secs = run_once()  # includes jit compilation at full shapes
    warm = [run_once() for _ in range(5)]
    jax_secs = min(warm)  # best-of: the same statistic as _timed
    jax_rps = n_rows / jax_secs

    def build_frame() -> Any:
        out = transform(src, jax_udf, schema="k:int,v2:float",
                        engine=engine, as_fugue=True)
        return aggregate(
            out, partition_by="k",
            s=ff.sum(col("v2")), m=ff.avg(col("v2")), c=ff.count(col("v2")),
            engine=engine, as_fugue=True,
        )

    # transform reads k+v, writes v2; groupby reads k+v2 (5 x 4B streams)
    roofline = _roofline(build_frame, n_rows * 20, engine=engine)

    memory_block = _governance_overhead(
        pd.DataFrame({"k": keys[:n_native], "v": values[:n_native]}),
        jax_udf,
        n_native,
    )

    observability_block = _observability_overhead(
        pd.DataFrame({"k": keys[:n_native], "v": values[:n_native]}),
        jax_udf,
        n_native,
    )

    profiler_block = _profiler_overhead(
        pd.DataFrame({"k": keys[:n_native], "v": values[:n_native]}),
        jax_udf,
        n_native,
    )

    optimizer_block = _optimizer_pipeline_bench(_scale(2_000_000))

    return {
        "metric": "transform_groupby_rows_per_sec",
        "value": round(jax_rps, 1),
        "unit": "rows/sec",
        "vs_baseline": round(jax_rps / native_rps, 2),
        "vs_baseline_pinned": (
            None  # pinned denominators are full-scale measurements
            if _SMALL
            else round(jax_rps / _PINNED_NATIVE_RPS["headline"], 2)
        ),
        "detail": {
            "rows_jax": n_rows,
            "rows_native": n_native,
            "groups": n_groups,
            "jax_secs": round(jax_secs, 4),
            "jax_cold_secs": round(cold_secs, 4),
            "native_secs": round(native_secs, 4),
            "native_rows_per_sec": round(native_rps, 1),
            "roofline": roofline,
            "strategy_counts": dict(engine.strategy_counts),
            "memory": memory_block,
            "observability": observability_block,
            "profiler": profiler_block,
            "optimizer": optimizer_block,
            "devices": len(jax.devices()),
            "platform": jax.devices()[0].platform,
            "notes": (
                "vs_baseline uses the same min-of-warm statistic on both "
                "sides; vs_baseline_pinned divides by the dated pinned "
                "denominator (_PINNED_NATIVE_RPS) so rounds compare "
                "without the native rerun's ambient variance. "
                "jax_cold_secs is THIS process's first full-shape run "
                "AFTER a forcing persist (staging lands in setup, where "
                "the reference's in-memory input also lives): trace + "
                "compile (or compile-cache load) + first dispatch. "
                "detail.roofline splits warm time into one scalar "
                "sync round trip vs device-resident compute, with a "
                "bytes-touched lower bound on achieved bandwidth. "
                "Small configs run on the engine's host CPU-XLA "
                "placement tier (fugue.jax.placement=auto)."
            ),
        },
    }


def _config1_map_letter_to_food() -> Dict[str, Any]:
    """BASELINE config 1: the README map_letter_to_food transform (string
    mapping UDF). Each engine runs its idiomatic UDF (same convention as
    configs 2/5): pandas ``.map`` on native; the dictionary-code compiled
    map ABI on jax — codes pass through unchanged and the 3-entry decode
    table is remapped on host, so the transform is O(|dictionary|) host
    work plus the arrow export."""
    import jax
    import numpy as np
    import pandas as pd

    from fugue_tpu import transform
    from fugue_tpu.execution import make_execution_engine

    n = _scale(2_000_000)
    mapping = {"A": "Apple", "B": "Banana", "C": "Carrot"}
    pdf = pd.DataFrame(
        {"id": np.arange(n), "value": np.random.default_rng(0).choice(
            ["A", "B", "C"], n)}
    )

    def map_letter_to_food(df: pd.DataFrame, mp: dict) -> pd.DataFrame:
        df["value"] = df["value"].map(mp)
        return df

    def jax_map_letter(arrs: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        d = arrs["_value_dict"]
        remapped = np.array(
            [mapping.get(s, s) for s in d.tolist()], dtype=object
        )
        return {
            "id": arrs["id"],
            "value": arrs["value"],
            "_value_dict": remapped,
        }

    native = make_execution_engine("native")
    jax_e = make_execution_engine("jax")
    jsrc = jax_e.to_df(pdf)  # pre-staged source, same as configs 2/3

    def run_native() -> None:
        transform(
            pdf, map_letter_to_food, schema="*",
            params=dict(mp=mapping), engine=native, as_fugue=True,
        ).as_local()

    def run_jax() -> None:
        transform(
            jsrc, jax_map_letter, schema="*", engine=jax_e, as_fugue=True
        ).as_local()

    res = _pair(n, run_native, run_jax, "1_map_letter_to_food")
    # Quantify the auto-placement tradeoff per round. The
    # row above runs placement=auto (this config lands on the host
    # CPU-XLA tier); rerun with the accelerator tier FORCED so both sides
    # of the policy are measured, not asserted. On CPU-only boxes the
    # "device" tier IS the host mesh, so the two rows converge.
    forced = make_execution_engine("jax", {"fugue.jax.placement": "device"})
    fsrc = forced.persist(forced.to_df(pdf))  # stage outside the timing

    def run_forced() -> None:
        transform(
            fsrc, jax_map_letter, schema="*", engine=forced, as_fugue=True
        ).as_local()

    forced_secs = _timed(run_forced)
    res["placement"] = {
        "auto": {
            "jax_secs": res["jax_secs"],
            "backend": jsrc.native.mesh.devices.flat[0].platform,
        },
        "tpu": {
            "jax_secs": round(forced_secs, 4),
            "jax_rows_per_sec": round(n / forced_secs, 1),
            "backend": fsrc.native.mesh.devices.flat[0].platform,
        },
    }
    return res


def _config2_partition_udf() -> Dict[str, Any]:
    """BASELINE config 2: 10M-row vectorized UDF with partition_by."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pandas as pd

    from fugue_tpu import transform
    from fugue_tpu.execution import make_execution_engine

    n = _scale(10_000_000)
    rng = np.random.default_rng(1)
    pdf = pd.DataFrame(
        {
            "k": rng.integers(0, 512, n).astype(np.int32),
            "v": rng.random(n).astype(np.float32),
        }
    )

    def pandas_udf(df: pd.DataFrame) -> pd.DataFrame:
        return df.assign(z=(df["v"] - df["v"].mean()))

    def jax_udf(arrs: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        seg, num, valid = (
            arrs["_segment_ids"], arrs["_num_segments"], arrs["_row_valid"]
        )
        v = jnp.where(valid, arrs["v"], 0.0)
        cnt = jax.ops.segment_sum(
            jnp.where(valid, 1.0, 0.0), seg, num_segments=num
        )
        mean = jax.ops.segment_sum(v, seg, num_segments=num) / jnp.maximum(
            cnt, 1.0
        )
        return {
            "k": arrs["k"], "v": arrs["v"],
            "z": arrs["v"] - mean[jnp.clip(seg, 0, num - 1)],
        }

    native = make_execution_engine("native")
    jax_e = make_execution_engine("jax")
    jsrc = jax_e.to_df(pdf)

    def run_native() -> None:
        transform(
            pdf, pandas_udf, schema="*,z:float",
            partition={"by": ["k"]}, engine=native, as_fugue=True,
        ).as_local()

    def run_jax() -> None:
        out = transform(
            jsrc, jax_udf, schema="k:int,v:float,z:float",
            partition={"by": ["k"]}, engine=jax_e, as_fugue=True,
        )
        import jax as _j

        # honest endpoint: ALL device output columns come back (same
        # statistic as the headline), not just the first
        arrs = [c.data for c in out.native.columns.values() if c.on_device]
        if out.native.row_valid is not None:
            arrs.append(out.native.row_valid)
        _j.device_get(arrs)

    return _pair(n, run_native, run_jax, "2_partition_udf")


def _config3_fuguesql_groupby() -> Dict[str, Any]:
    """BASELINE config 3: FugueSQL SELECT + GROUP BY sum/mean/count."""
    import numpy as np
    import pandas as pd

    from fugue_tpu.execution import make_execution_engine
    from fugue_tpu.workflow.api import raw_sql

    n = _scale(10_000_000)
    rng = np.random.default_rng(2)
    pdf = pd.DataFrame(
        {
            "k": rng.integers(0, 256, n).astype(np.int32),
            "v": rng.random(n).astype(np.float32),
        }
    )
    native = make_execution_engine("native")
    jax_e = make_execution_engine("jax")
    jsrc = jax_e.to_df(pdf)

    def run(engine: Any, src: Any) -> None:
        raw_sql(
            "SELECT k, SUM(v) AS s, AVG(v) AS m, COUNT(*) AS c FROM", src,
            "GROUP BY k", engine=engine, as_fugue=True,
        ).as_local()

    return _pair(
        n, lambda: run(native, pdf), lambda: run(jax_e, jsrc),
        "3_fuguesql_groupby",
    )


def _config3b_sql_join() -> Dict[str, Any]:
    """Supplementary (verdict r3 item 3): FugueSQL two-table equi-join +
    GROUP BY — the shape that lowers through the device relational layer
    (joins in relational.py) instead of the host SELECT runner."""
    import numpy as np
    import pandas as pd

    from fugue_tpu.execution import make_execution_engine
    from fugue_tpu.workflow.api import raw_sql

    n = _scale(5_000_000)
    rng = np.random.default_rng(5)
    facts = pd.DataFrame(
        {
            "k": rng.integers(0, 256, n).astype(np.int32),
            "v": rng.random(n).astype(np.float32),
        }
    )
    dims = pd.DataFrame(
        {
            "k": np.arange(256, dtype=np.int32),
            "w": rng.random(256).astype(np.float32),
        }
    )
    native = make_execution_engine("native")
    jax_e = make_execution_engine("jax")
    jf, jd = jax_e.to_df(facts), jax_e.to_df(dims)

    def run(engine: Any, f: Any, d: Any) -> Any:
        return raw_sql(
            "SELECT f.k, SUM(v) AS s, AVG(w) AS m, COUNT(*) AS c FROM", f,
            "AS f JOIN", d, "AS d ON f.k = d.k GROUP BY f.k",
            engine=engine, as_fugue=True,
        )

    res = _pair(
        n,
        lambda: run(native, facts, dims).as_local(),
        lambda: run(jax_e, jf, jd).as_local(),
        "3b_sql_join",
    )
    # snapshot BEFORE the roofline probe re-runs the query
    res["jax_fallbacks"] = dict(jax_e.fallbacks)
    # join reads k+v, gathers w + validity; groupby reads k+v+w
    res["roofline"] = _roofline(lambda: run(jax_e, jf, jd), n * 20)
    return res


def _config4_cotransform() -> Dict[str, Any]:
    """BASELINE config 4: cotransform inner zip+comap of two partitioned
    dataframes (the path rebuilt without serialization)."""
    import numpy as np
    import pandas as pd

    from fugue_tpu.execution import make_execution_engine

    groups = 2_000 if not _SMALL else 100
    per = 50
    n = groups * per
    rng = np.random.default_rng(3)
    a = pd.DataFrame(
        {
            "k": np.repeat(np.arange(groups, dtype=np.int64), per),
            "v": rng.random(n),
        }
    )
    b = pd.DataFrame(
        {
            "k": np.arange(groups, dtype=np.int64),
            "w": rng.random(groups),
        }
    )

    def cm_pandas(dfa: pd.DataFrame, dfb: pd.DataFrame) -> pd.DataFrame:
        va, vb = dfa, dfb
        return pd.DataFrame(
            {
                "k": [int(va.k.iloc[0])],
                "s": [float(va.v.sum() + (vb.w.sum() if len(vb) else 0.0))],
            }
        )

    import jax as _jax
    import jax.numpy as jnp

    def cm_jax(
        da: Dict[str, _jax.Array], db: Dict[str, _jax.Array]
    ) -> Dict[str, _jax.Array]:
        # the compiled-comap ABI: per-key work as segment reductions over
        # the shared segment space (comap_compiled.py)
        S = da["_num_segments"]
        sa = _jax.ops.segment_sum(
            jnp.where(da["_row_valid"], da["v"], 0.0),
            da["_segment_ids"], num_segments=S,
        )
        sb = _jax.ops.segment_sum(
            jnp.where(db["_row_valid"], db["w"], 0.0),
            db["_segment_ids"], num_segments=S,
        )
        k = _jax.ops.segment_max(
            jnp.where(da["_row_valid"], da["k"].astype(jnp.int32), -(2**31)),
            da["_segment_ids"], num_segments=S,
        )
        return {"k": k, "s": sa + sb}

    def run(engine: Any, cm: Any) -> None:
        from fugue_tpu.workflow import FugueWorkflow

        dag = FugueWorkflow()
        za = dag.df(a, "k:long,v:double")
        zb = dag.df(b, "k:long,w:double")
        z = za.partition_by("k").zip(zb)
        z.transform(cm, schema="k:long,s:double").yield_dataframe_as(
            "out", as_local=True
        )
        dag.run(engine)

    native = make_execution_engine("native")
    jax_e = make_execution_engine("jax")
    res = _pair(
        n, lambda: run(native, cm_pandas), lambda: run(jax_e, cm_jax),
        "4_cotransform",
    )
    res["jax_fallbacks"] = dict(jax_e.fallbacks)
    return res


def _config5_e2e_parquet() -> Dict[str, Any]:
    """BASELINE config 5: load parquet -> transform -> groupby -> save."""
    import numpy as np
    import pandas as pd

    from fugue_tpu.column import col
    from fugue_tpu.column import functions as ff
    from fugue_tpu.execution import make_execution_engine
    from fugue_tpu.execution.api import aggregate
    from fugue_tpu import transform

    n = _scale(5_000_000)
    rng = np.random.default_rng(4)
    pdf = pd.DataFrame(
        {
            "k": rng.integers(0, 128, n).astype(np.int32),
            "v": rng.random(n).astype(np.float32),
        }
    )
    tmp = tempfile.mkdtemp(prefix="fugue_bench_")
    src_path = os.path.join(tmp, "src.parquet")
    pdf.to_parquet(src_path)

    def pandas_udf(df: pd.DataFrame) -> pd.DataFrame:
        return df.assign(v2=df["v"] * 0.5)

    import jax as _jax
    import jax.numpy as jnp

    def jax_udf(arrs: Dict[str, _jax.Array]) -> Dict[str, _jax.Array]:
        return {"k": arrs["k"], "v2": arrs["v"] * jnp.float32(0.5)}

    engines = {
        "native": make_execution_engine("native"),
        "jax": make_execution_engine("jax"),
        # streamed ingest/save: record-batch decode overlaps per-shard
        # device staging (fugue.jax.io.batch_rows; ISSUE 2 tentpole)
        "jax_streamed": make_execution_engine(
            "jax", {"fugue.jax.io.batch_rows": max(n // 16, 65_536)}
        ),
    }

    def run(engine: Any, udf: Any, schema: str, out_name: str) -> None:
        e = engines[engine]  # reuse: jit caches live on the engine
        df = e.load_df(src_path, format_hint="parquet")
        out = transform(df, udf, schema=schema, engine=e, as_fugue=True)
        agg = aggregate(
            out, partition_by="k",
            s=ff.sum(col("v2")), c=ff.count(col("v2")),
            engine=e, as_fugue=True,
        )
        e.save_df(agg, os.path.join(tmp, out_name), format_hint="parquet")

    def _drain(df: Any) -> Any:
        """Force device residency so a phase boundary is honest (lazy
        ingest + async dispatch otherwise push work into later phases)."""
        import jax as __jax

        blocks = getattr(df, "blocks", None)
        if blocks is not None and not callable(blocks):
            from fugue_tpu.jax_backend.blocks import residency_arrays

            for arr in residency_arrays(blocks):
                __jax.block_until_ready(arr)
        return df

    def run_phases(engine: Any, udf: Any, schema: str, out_name: str) -> Dict[str, float]:
        """One decomposed pass: per-phase seconds with forced phase
        boundaries. Comparing `sum(phases)` with the pipelined e2e time
        (which never forces boundaries) makes the load/stage/save
        overlap win visible in the artifact."""
        e = engines[engine]
        t0 = time.perf_counter()
        df = _drain(e.load_df(src_path, format_hint="parquet"))
        t1 = time.perf_counter()
        out = transform(df, udf, schema=schema, engine=e, as_fugue=True)
        agg = _drain(aggregate(
            out, partition_by="k",
            s=ff.sum(col("v2")), c=ff.count(col("v2")),
            engine=e, as_fugue=True,
        ))
        t2 = time.perf_counter()
        e.save_df(agg, os.path.join(tmp, out_name), format_hint="parquet")
        t3 = time.perf_counter()
        return {
            "load_secs": round(t1 - t0, 4),
            "compute_secs": round(t2 - t1, 4),
            "save_secs": round(t3 - t2, 4),
            "sum_secs": round(t3 - t0, 4),
        }

    res = _pair(
        n,
        lambda: run("native", pandas_udf, "*,v2:float", "out_native.parquet"),
        lambda: run(
            "jax", jax_udf, "k:int,v2:float", "out_jax.parquet"
        ),
        pinned_key="5_e2e_parquet",
    )
    streamed_secs = _timed(
        lambda: run("jax_streamed", jax_udf, "k:int,v2:float",
                    "out_jax_s.parquet")
    )
    res["jax_streamed_secs"] = round(streamed_secs, 4)
    res["jax_streamed_rows_per_sec"] = round(n / streamed_secs, 1)
    res["streamed_vs_eager"] = round(res["jax_secs"] / streamed_secs, 2)
    res["phases"] = {
        name: run_phases(name, udf, schema, out)
        for name, udf, schema, out in [
            ("native", pandas_udf, "*,v2:float", "out_native.parquet"),
            ("jax", jax_udf, "k:int,v2:float", "out_jax.parquet"),
            ("jax_streamed", jax_udf, "k:int,v2:float", "out_jax_s.parquet"),
        ]
    }
    # ISSUE 10: optimizer on/off dual rows — the workflow-layer
    # narrow-consumer variant of this pipeline at the same scale
    res["optimizer"] = _optimizer_pipeline_bench(n)
    return res


def _config6_serving_daemon() -> Dict[str, Any]:
    """Sustained-throughput serving scenario (ISSUE r11): concurrent
    clients over real HTTP against ONE in-process daemon with a shared
    persistent jax engine — each client's hot table is saved once and
    then queried repeatedly (groupby SQL over the device-resident
    catalog frame, no re-ingest). Reports queries/sec and p50/p99
    request latency alongside the batch configs' rows/sec."""
    import numpy as np
    import pandas as pd

    from fugue_tpu.serve import ServeClient, ServeDaemon

    clients = 4
    queries_per_client = 8
    rows = _scale(1_000_000)
    agg_sql = "SELECT k, SUM(v) AS s, COUNT(*) AS c FROM t GROUP BY k"
    out: Dict[str, Any] = {
        "clients": clients,
        "queries_per_client": queries_per_client,
        "rows_per_table": rows,
        # this block measures the default FIFO queue; config 12 runs the
        # predictive scheduler, so the headline rows stay comparable
        "scheduler": "fifo",
    }
    import threading as _threading

    # result cache OFF here: this block's qps/p50/p99 measure serving
    # EXECUTION (comparable with prior rounds); the cached fast path is
    # measured separately by warm_resubmission below
    with ServeDaemon(
        {
            "fugue.serve.max_concurrent": clients,
            "fugue.serve.result_cache": False,
        }
    ) as daemon:
        host, port = daemon.address
        rng = np.random.default_rng(11)
        latencies: list = []
        errors: list = []
        lat_lock = _threading.Lock()

        # hot-table setup + program warmup, UNMEASURED: each client's
        # table is saved once and stays device-resident in the catalog;
        # the timed loop below is pure serving traffic
        handles = []
        for i in range(clients):
            c = ServeClient(host, port, timeout=600)
            sid = c.create_session()
            pdf = pd.DataFrame(
                {
                    "k": rng.integers(0, 64, rows).astype(np.int64),
                    "v": rng.random(rows),
                }
            )
            daemon.sessions.get(sid).save_table(
                "t", daemon.engine.to_df(pdf)
            )
            c.sql(sid, agg_sql)  # warm the compiled programs
            handles.append((c, sid))

        def one_client(c: Any, sid: str) -> None:
            try:
                mine = []
                for _ in range(queries_per_client):
                    t0 = time.perf_counter()
                    r = c.sql(sid, agg_sql)
                    mine.append((time.perf_counter() - t0) * 1000.0)
                    if r["status"] != "done":
                        errors.append(r.get("error"))
                with lat_lock:
                    latencies.extend(mine)
                c.close_session(sid)
            except Exception as ex:  # pragma: no cover - surfaced in json
                errors.append(repr(ex))

        threads = [
            _threading.Thread(target=one_client, args=h) for h in handles
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        status = daemon.status()
        out["errors"] = errors
        total = clients * queries_per_client
        out["queries"] = total
        out["wall_secs"] = round(wall, 4)
        out["queries_per_sec"] = round(total / wall, 2) if wall > 0 else 0.0
        if latencies:
            out["p50_ms"] = round(float(np.percentile(latencies, 50)), 2)
            out["p99_ms"] = round(float(np.percentile(latencies, 99)), 2)
            out["mean_ms"] = round(float(np.mean(latencies)), 2)
        out["jobs"] = status["jobs"]
        out["fault_stats"] = status["fault_stats"]
    out["warm_resubmission"] = _serving_warm_resubmission(
        _scale(1_000_000), agg_sql
    )
    out["restart_recovery"] = _serving_restart_recovery(
        clients, _scale(200_000), agg_sql
    )
    return out


def _serving_warm_resubmission(rows: int, agg_sql: str) -> Dict[str, Any]:
    """Warm-resubmission scenario (ISSUE 10): the SAME query resubmitted
    on a hot session answers from the cross-request plan/result cache —
    no Python planning, no dispatch, no XLA compile. Runs its own
    default-conf daemon (the cache is ON by default; the main qps block
    above disables it to measure execution). Reports the plan-cache hit
    rate, the p50 latency delta vs the first (executed) submission, and
    the engine's plan-cache miss delta during the warm loop (the
    zero-recompiles proof)."""
    import numpy as np
    import pandas as pd

    from fugue_tpu.serve import ServeClient, ServeDaemon

    repeats = 16
    with ServeDaemon({"fugue.serve.max_concurrent": 2}) as daemon:
        host, port = daemon.address
        c = ServeClient(host, port, timeout=600)
        sid = c.create_session()
        rng = np.random.default_rng(23)
        pdf = pd.DataFrame(
            {
                "k": rng.integers(0, 64, rows).astype(np.int64),
                "v": rng.random(rows),
            }
        )
        daemon.sessions.get(sid).save_table("t", daemon.engine.to_df(pdf))
        t0 = time.perf_counter()
        first = c.sql(sid, agg_sql)
        first_ms = (time.perf_counter() - t0) * 1000.0
        assert first["status"] == "done", first
        plan_misses_before = daemon.engine.plan_cache_stats["misses"]
        warm_ms = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            r = c.sql(sid, agg_sql)
            warm_ms.append((time.perf_counter() - t0) * 1000.0)
            assert r["status"] == "done", r
        plan_miss_delta = (
            daemon.engine.plan_cache_stats["misses"] - plan_misses_before
        )
        st = daemon.status()
        sr = st["plan_cache"]["serve_result"]
        looked_up = sr.get("hit", 0) + sr.get("miss", 0)
        c.close_session(sid)
    p50 = float(np.percentile(warm_ms, 50))
    return {
        "rows": rows,
        "resubmissions": repeats,
        "first_ms": round(first_ms, 2),
        "warm_p50_ms": round(p50, 2),
        "p50_latency_delta_ms": round(first_ms - p50, 2),
        "warm_speedup": round(first_ms / max(p50, 1e-9), 2),
        "result_cache_hits": sr.get("hit", 0),
        "plan_cache_hit_rate": (
            round(sr.get("hit", 0) / looked_up, 4) if looked_up else 0.0
        ),
        "recompiles_during_warm": plan_miss_delta,
    }


def _serving_restart_recovery(
    tenants: int, rows: int, agg_sql: str
) -> Dict[str, Any]:
    """Restart-recovery scenario (ISSUE 7 + 11): a DURABLE daemon holding
    one hot table per tenant — now also backed by the persistent
    executable cache — is hard-killed mid-serving, then restarted on the
    same state path. Reports time-to-ready (journal load + session
    rehydration + executable pre-warm), the recovered session/hot-table
    counts, and ``time_to_first_query`` SPLIT into journal-reload /
    cache-load / compile / dispatch phases (the compile phase must read
    ~0 when the pre-warm did its job)."""
    import tempfile

    import numpy as np
    import pandas as pd

    from fugue_tpu.optimize import flush_persists, get_plan_cache
    from fugue_tpu.serve import ServeClient, ServeDaemon

    out: Dict[str, Any] = {"tenants": tenants, "rows_per_table": rows}
    with tempfile.TemporaryDirectory() as state_dir:
        conf = {
            "fugue.serve.max_concurrent": tenants,
            "fugue.serve.state_path": os.path.join(state_dir, "state"),
            # ISSUE 11: the executable disk tier + daemon pre-warm make
            # the restart's first query compile-free
            "fugue.optimize.cache.dir": os.path.join(state_dir, "xc"),
        }
        d1 = ServeDaemon(conf).start()
        host, port = d1.address
        rng = np.random.default_rng(7)
        sids = []
        for _ in range(tenants):
            c = ServeClient(host, port, timeout=600)
            sid = c.create_session()
            pdf = pd.DataFrame(
                {
                    "k": rng.integers(0, 64, rows).astype(np.int64),
                    "v": rng.random(rows),
                }
            )
            d1.sessions.get(sid).save_table("t", d1.engine.to_df(pdf))
            sids.append(sid)
        for sid in sids:
            ServeClient(host, port, timeout=600).sql(sid, agg_sql)
        flush_persists()  # executables durable before the "kill -9"
        d1._hard_kill()  # no drain, no final journal write
        # the plan cache is process-wide: clearing it makes the restart
        # below equivalent to a fresh process (disk is the only carry)
        get_plan_cache().clear()

        t0 = time.perf_counter()
        d2 = ServeDaemon(conf).start()
        out["time_to_healthy_secs"] = round(time.perf_counter() - t0, 4)
        while not d2.ready and time.perf_counter() - t0 < 120:
            time.sleep(0.01)
        out["time_to_ready_secs"] = round(time.perf_counter() - t0, 4)
        try:
            c2 = ServeClient(host, d2.address[1], timeout=600)
            st = c2.status()
            out["recovered_sessions"] = st["recovery"]["sessions"]
            # first query per tenant lazily reloads the fingerprint-
            # verified artifact into the device catalog
            t1 = time.perf_counter()
            ok = 0
            first_query_secs = None
            for sid in sids:
                q0 = time.perf_counter()
                snap = c2.sql(sid, agg_sql)
                if first_query_secs is None:
                    first_query_secs = round(time.perf_counter() - q0, 4)
                if snap["status"] == "done" and "t" in c2.session(sid)[
                    "tables"
                ]:
                    ok += 1
            out["reload_all_tables_secs"] = round(
                time.perf_counter() - t1, 4
            )
            out["recovered_hot_tables"] = ok
            # ISSUE 11 phase split: journal-reload / cache-load from
            # startup, compile / dispatch from the first executed query
            cold = c2.status().get("cold_start", {})
            phases = dict(cold.get("phases", {}))
            fq = cold.get("first_query", {})
            out["time_to_first_query"] = {
                "total_secs": first_query_secs,
                "journal_reload_secs": phases.get("journal_reload_secs"),
                "cache_load_secs": phases.get("cache_load_secs"),
                "prewarmed_executables": phases.get(
                    "prewarmed_executables"
                ),
                "compile_secs": fq.get("compile_secs"),
                "dispatch_secs": fq.get("dispatch_secs"),
                "disk_load_secs": fq.get("disk_load_secs"),
                "xla_compiles": fq.get("xla_compiles"),
            }
        finally:
            d2.stop()
    return out


_COLD_START_SCRIPT = r"""
import json, os, sys, time
t_start = time.perf_counter()
import numpy as np
from fugue_tpu.column import col
from fugue_tpu.column import functions as ff
from fugue_tpu.execution import make_execution_engine
from fugue_tpu.execution.api import aggregate
from fugue_tpu.optimize import flush_persists
t_import = time.perf_counter()

src, out_path, cache_dir, batch_rows = sys.argv[1:5]
conf = {"fugue.jax.io.batch_rows": int(batch_rows)}
if cache_dir:
    conf["fugue.optimize.cache.dir"] = cache_dir
t0 = time.perf_counter()
e = make_execution_engine("jax", conf)
df = e.load_df(src, format_hint="parquet")
agg = aggregate(
    e.filter(df, col("k") < 96), partition_by="k",
    s=ff.sum(col("v")), c=ff.count(col("v")),
    engine=e, as_fugue=True,
)
e.save_df(agg, out_path, format_hint="parquet")
t1 = time.perf_counter()
flush_persists()
print(json.dumps({
    "import_secs": round(t_import - t_start, 4),
    "pipeline_secs": round(t1 - t0, 4),
    "process_secs": round(time.perf_counter() - t_start, 4),
    "compile_cache": e.compile_cache_stats,
    "exec_cache": e.exec_cache_stats,
}))
"""


def _config7_cold_start() -> Dict[str, Any]:
    """Cold-start scenario (ISSUE 11): the SAME pipeline end-to-end in
    FRESH OS processes — executable cache off, cache on with an empty
    dir (pays compile + persists), and cache on warm (the acceptance
    row: pipeline wall <1 s on this container with 0 XLA compiles,
    counter-verified). ``import_secs`` is reported separately: the
    interpreter + jax import cost is shared by every python process and
    not something the cache can (or should) hide."""
    import subprocess
    import sys as _sys

    import numpy as np
    import pandas as pd

    n = _scale(2_000_000)
    rng = np.random.default_rng(17)
    tmp = tempfile.mkdtemp(prefix="fugue_cold_")
    src = os.path.join(tmp, "src.parquet")
    pd.DataFrame(
        {
            "k": rng.integers(0, 128, n).astype(np.int32),
            "v": rng.random(n).astype(np.float32),
        }
    ).to_parquet(src)
    # a FIXED path (a moving one never hits), emptied so the "cold" row
    # really starts cold
    cache_dir = os.path.join(_CHECKOUT, ".jax_cache", "cold_start_exec")
    shutil.rmtree(cache_dir, ignore_errors=True)
    batch_rows = str(max(n // 16, 65_536))

    def run(tag: str, cache: str) -> Dict[str, Any]:
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        # the controlled comparison must not let an inherited legacy
        # alias env var leak into the cache-off variant
        env.pop("FUGUE_JAX_COMPILE_CACHE", None)
        out = subprocess.run(
            [
                _sys.executable, "-c", _COLD_START_SCRIPT,
                src, os.path.join(tmp, f"out_{tag}.parquet"),
                cache, batch_rows,
            ],
            capture_output=True, text=True, timeout=900, env=env,
        )
        if out.returncode != 0:  # surfaced in the artifact, not fatal
            return {"error": out.stderr[-1500:]}
        return json.loads(out.stdout.strip().splitlines()[-1])

    res: Dict[str, Any] = {"rows": n}
    res["cache_off"] = run("off", "")
    res["cache_on_cold"] = run("cold", cache_dir)  # compiles + persists
    res["cache_on_warm"] = run("warm", cache_dir)  # the fresh-process hit
    warm = res["cache_on_warm"]
    off = res["cache_off"]
    if "pipeline_secs" in warm and "pipeline_secs" in off:
        res["warm_vs_off_speedup"] = round(
            off["pipeline_secs"] / max(warm["pipeline_secs"], 1e-9), 2
        )
        res["warm_xla_compiles"] = warm["compile_cache"]["misses"]
        res["warm_under_1s"] = warm["pipeline_secs"] < 1.0
    return res


_SCALING_SCRIPT = r"""
import json, sys, time
n_dev, rows, jrows = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
import numpy as np
import pandas as pd
import jax
from fugue_tpu.column import col
from fugue_tpu.column import functions as ff
from fugue_tpu.collections.partition import PartitionSpec
from fugue_tpu.jax_backend import JaxExecutionEngine

assert len(jax.devices()) == n_dev, (len(jax.devices()), n_dev)
# shuffle pinned ON: this config measures the sharded relational path
# itself (auto would decline the small BENCH_SMALL shapes)
e = JaxExecutionEngine({"fugue.jax.shuffle": "on"})

def gb_frame(seed):
    # every frame carries EXACTLY the full 512-key domain (permuted):
    # num_segments is a static of the compiled program, so a randomly
    # missing key would read as a spurious recompile on the warm run
    r = np.random.default_rng(seed)
    return pd.DataFrame({
        "k": r.permutation(np.arange(rows, dtype=np.int64) % 512),
        "v": r.random(rows),
    })

aggs = [
    ff.sum(col("v")).alias("s"),
    ff.count(col("v")).alias("c"),
    ff.min(col("v")).alias("mn"),
]
spec = PartitionSpec(by=["k"])
# distinct pre-ingested frames per run: identical shapes share compiled
# programs, distinct data defeats any result memoization
gb = [e.to_df(gb_frame(s)) for s in (1, 2, 3)]
e.aggregate(gb[0], spec, aggs).as_array()  # compile + warm
m0 = e.compile_cache_stats["misses"]
best = float("inf")
for _ in range(3):  # best-of-6 damps the 1-core container's jitter
    for d in gb[1:]:
        t0 = time.perf_counter()
        e.aggregate(d, spec, aggs).as_array()
        best = min(best, time.perf_counter() - t0)
gb_rps = rows / best
gb_zero = e.compile_cache_stats["misses"] == m0
del gb  # release the group-by frames' device buffers before the join

jdom = max(jrows // 4, 64)

def j_frame(seed, n):
    # full key domain on both sides, same determinism rationale. The
    # domain keeps multiplicity low (right side: exactly 2 rows/key,
    # output ~2x left) so the timing measures the relational path, not
    # a many-to-many row explosion; 2 rows/key also keeps the right
    # side off the unique-right fast path so the sharded count program
    # actually runs
    r = np.random.default_rng(seed)
    return pd.DataFrame({
        "k": r.permutation(np.arange(n, dtype=np.int64) % jdom),
        "v": r.random(n),
    })

right = e.to_df(j_frame(9, jrows // 2).rename(columns={"v": "w"}))
lefts = [e.to_df(j_frame(s, jrows)) for s in (4, 5, 6)]
e.join(lefts[0], right, how="inner", on=["k"]).count()  # compile + warm
m1 = e.compile_cache_stats["misses"]
jbest = float("inf")
for _ in range(3):
    for d in lefts[1:]:
        t0 = time.perf_counter()
        e.join(d, right, how="inner", on=["k"]).count()
        jbest = min(jbest, time.perf_counter() - t0)
j_rps = jrows / jbest
j_zero = e.compile_cache_stats["misses"] == m1
print(json.dumps({
    "devices": n_dev,
    "groupby_rows_per_sec": round(gb_rps),
    "join_rows_per_sec": round(j_rps),
    "zero_recompile_warm": bool(gb_zero and j_zero),
    "shuffle_counts": e.shuffle_counts if n_dev > 1 else {},
}))
"""


def _config10_scaling() -> Dict[str, Any]:
    """Multi-device scaling curve (ISSUE 16): the SAME shuffle-on
    group-by and join workloads in fresh processes at devices=1/2/4/8
    (CPU via ``--xla_force_host_platform_device_count``), reporting
    rows/sec per point and ``parallel_efficiency`` per workload:
    ``(rps_n / rps_1) / min(n, cpu_cores)``. The min(n, cores)
    normalizer makes the number honest on this container: forced host
    devices beyond the physical core count cannot add real parallelism,
    so a point at n > cores measures shuffle OVERHEAD (efficiency ~1.0
    = the sharded path costs nothing extra), while n <= cores measures
    true scale-out. ``zero_recompile_warm`` asserts the one-trace
    invariant held at every device count."""
    import subprocess
    import sys as _sys

    rows = _scale(1_000_000)
    jrows = _scale(400_000)
    cores = os.cpu_count() or 1

    def run(n_dev: int) -> Dict[str, Any]:
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        flags = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")
        ]
        flags.append(f"--xla_force_host_platform_device_count={n_dev}")
        env["XLA_FLAGS"] = " ".join(flags)
        out = subprocess.run(
            [
                _sys.executable, "-c", _SCALING_SCRIPT,
                str(n_dev), str(rows), str(jrows),
            ],
            capture_output=True, text=True, timeout=900, env=env,
        )
        if out.returncode != 0:  # surfaced in the artifact, not fatal
            return {"devices": n_dev, "error": out.stderr[-1500:]}
        return json.loads(out.stdout.strip().splitlines()[-1])

    # TWO interleaved sweeps, merged per point by best rows/sec: the
    # points are measured in separate subprocesses minutes apart, and on
    # a small shared box the machine-state epochs between them swing
    # single measurements by tens of percent — a second decorrelated
    # pass damps exactly the noise that best-of-N inside one process
    # cannot see
    merged: Dict[int, Dict[str, Any]] = {}
    for _sweep in range(2):
        for n in (1, 2, 4, 8):
            p = run(n)
            prev = merged.get(n)
            if prev is None or "error" in prev:
                merged[n] = p
            elif "error" not in p:
                for k in ("groupby_rows_per_sec", "join_rows_per_sec"):
                    prev[k] = max(prev[k], p[k])
                prev["zero_recompile_warm"] = (
                    prev["zero_recompile_warm"] and p["zero_recompile_warm"]
                )
    points = [merged[n] for n in (1, 2, 4, 8)]
    res: Dict[str, Any] = {
        "rows": rows,
        "join_rows": jrows,
        "cpu_cores": cores,
        "points": points,
        "efficiency_normalizer": "min(devices, cpu_cores)",
    }
    base = points[0]
    eff: Dict[str, Dict[str, float]] = {}
    if "error" not in base:
        for p in points[1:]:
            if "error" in p:
                continue
            n = p["devices"]
            denom = float(min(n, cores))
            eff[str(n)] = {
                "groupby": round(
                    p["groupby_rows_per_sec"]
                    / max(base["groupby_rows_per_sec"], 1)
                    / denom,
                    3,
                ),
                "join": round(
                    p["join_rows_per_sec"]
                    / max(base["join_rows_per_sec"], 1)
                    / denom,
                    3,
                ),
            }
    res["parallel_efficiency"] = eff
    res["zero_recompile_warm"] = all(
        p.get("zero_recompile_warm", False)
        for p in points
        if "error" not in p
    )
    return res


def _config8_serving_fleet() -> Dict[str, Any]:
    """Fleet serving scenario (ISSUE 13): aggregate qps + p99 through
    the front-tier router at replicas=1 and replicas=2 (each replica
    owns its own engine; both caches off so the numbers measure serving
    EXECUTION, comparable with config 6), plus a rolling restart of the
    2-replica fleet under a continuous client loop — reporting
    failed_calls (the zero-drop contract) and migration_secs (the
    journal-adoption handoff cost)."""
    import tempfile
    import threading as _threading

    import numpy as np
    import pandas as pd

    from fugue_tpu.serve import ServeClient, ServeFleet

    clients = 4
    queries_per_client = 6
    rows = _scale(200_000)
    agg_sql = "SELECT k, SUM(v) AS s, COUNT(*) AS c FROM t GROUP BY k"
    out: Dict[str, Any] = {
        "clients": clients,
        "queries_per_client": queries_per_client,
        "rows_per_table": rows,
        # this block measures the default FIFO queue; config 12 runs the
        # predictive scheduler, so the fleet rows stay comparable
        "scheduler": "fifo",
    }

    def _fleet_conf(tmp: str) -> Dict[str, Any]:
        return {
            "fugue.serve.state_path": tmp + "/state",
            "fugue.serve.max_concurrent": clients,
            "fugue.serve.breaker.threshold": 0,
            # execution, not cache reads: both result tiers off
            "fugue.serve.result_cache": False,
            "fugue.serve.fleet.result_cache_dir": "",
            "fugue.serve.fleet.health_interval": 0.1,
            "fugue.serve.drain_timeout": 30.0,
        }

    def _setup_tenants(fleet: Any) -> list:
        rng = np.random.default_rng(13)
        handles = []
        for _ in range(clients):
            c = ServeClient([fleet.address], retries=10, timeout=600)
            sid = c.create_session()
            pdf = pd.DataFrame(
                {
                    "k": rng.integers(0, 64, rows).astype(np.int64),
                    "v": rng.random(rows),
                }
            )
            # hot-table setup + program warmup, UNMEASURED (config 6
            # idiom): saved once via the owning replica's engine, then
            # queried repeatedly through the router
            rid = fleet.router.affinity()[sid]
            daemon = fleet.replica(rid)
            daemon.sessions.get(sid).save_table(
                "t", daemon.engine.to_df(pdf)
            )
            c.sql(sid, agg_sql)  # warm the compiled programs
            handles.append((c, sid))
        return handles

    def _qps_block(n_replicas: int) -> Dict[str, Any]:
        tmp = tempfile.mkdtemp(prefix="fugue_fleet_bench_")
        res: Dict[str, Any] = {"replicas": n_replicas}
        latencies: list = []
        errors: list = []
        lat_lock = _threading.Lock()
        with ServeFleet(_fleet_conf(tmp), replicas=n_replicas) as fleet:
            handles = _setup_tenants(fleet)

            def one_client(c: Any, sid: str) -> None:
                try:
                    mine = []
                    for _ in range(queries_per_client):
                        t0 = time.perf_counter()
                        r = c.sql(sid, agg_sql)
                        mine.append((time.perf_counter() - t0) * 1000.0)
                        if r["status"] != "done":
                            errors.append(r.get("error"))
                    with lat_lock:
                        latencies.extend(mine)
                except Exception as ex:  # pragma: no cover - in json
                    errors.append(repr(ex))

            threads = [
                _threading.Thread(target=one_client, args=h)
                for h in handles
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            res["sessions_per_replica"] = fleet.router.describe()[
                "sessions_per_replica"
            ]
        total = clients * queries_per_client
        res["errors"] = errors
        res["queries"] = total
        res["wall_secs"] = round(wall, 4)
        res["queries_per_sec"] = (
            round(total / wall, 2) if wall > 0 else 0.0
        )
        if latencies:
            res["p50_ms"] = round(float(np.percentile(latencies, 50)), 2)
            res["p99_ms"] = round(float(np.percentile(latencies, 99)), 2)
        return res

    def _rolling_restart_block() -> Dict[str, Any]:
        tmp = tempfile.mkdtemp(prefix="fugue_fleet_bench_rr_")
        res: Dict[str, Any] = {"replicas": 2}
        stop = _threading.Event()
        failed: list = []
        completed: list = []
        with ServeFleet(_fleet_conf(tmp), replicas=2) as fleet:
            handles = _setup_tenants(fleet)

            def loop(c: Any, sid: str) -> None:
                while not stop.is_set():
                    try:
                        r = c.sql(sid, agg_sql)
                        (completed if r["status"] == "done" else failed
                         ).append(sid)
                    except Exception as ex:  # pragma: no cover
                        failed.append(repr(ex))
                    time.sleep(0.01)

            threads = [
                _threading.Thread(target=loop, args=h) for h in handles
            ]
            for t in threads:
                t.start()
            time.sleep(0.5)  # continuous load established
            stats = fleet.rolling_restart()
            time.sleep(0.5)  # ...and keeps flowing on the fresh fleet
            stop.set()
            for t in threads:
                t.join(timeout=60)
        res["failed_calls"] = len(failed)
        res["completed_calls"] = len(completed)
        res["migrated_sessions"] = stats["migrated_sessions"]
        res["migration_secs"] = stats["migration_secs"]
        res["restart_secs"] = stats["secs"]
        return res

    out["replicas_1"] = _qps_block(1)
    out["replicas_2"] = _qps_block(2)
    out["rolling_restart"] = _rolling_restart_block()
    return out


def _config9_continuous() -> Dict[str, Any]:
    """Continuous execution (ISSUE 15): a standing pipeline tails
    arriving parquet files and maintains a serve session table as a
    materialized view. Reports sustained micro-batch throughput
    (fold rows/sec across the waves), end-to-end freshness latency
    (file LANDS on storage -> refreshed view QUERYABLE over HTTP with
    the new data), the zero-recompile counter contract (one XLA trace
    total across all micro-batches), and exact parity of the final view
    with the one-shot batch aggregate over the full file union."""
    import os as _os
    import tempfile

    import numpy as np
    import pandas as pd
    import pyarrow as _pa
    import pyarrow.parquet as _pq

    from fugue_tpu.serve import ServeClient, ServeDaemon

    waves = 5
    rows_per_wave = _scale(80_000)
    tmp = tempfile.mkdtemp(prefix="fugue_stream_bench_")
    src = _os.path.join(tmp, "in")
    _os.makedirs(src)
    rng = np.random.default_rng(15)
    out: Dict[str, Any] = {
        "waves": waves,
        "rows_per_wave": rows_per_wave,
    }

    def land(i: int) -> pd.DataFrame:
        pdf = pd.DataFrame(
            {
                "k": rng.integers(0, 64, rows_per_wave).astype(np.int64),
                "v": rng.random(rows_per_wave),
            }
        )
        t = _os.path.join(src, f".w{i}.tmp")
        _pq.write_table(_pa.Table.from_pandas(pdf, preserve_index=False), t)
        _os.replace(t, _os.path.join(src, f"w{i}.parquet"))
        return pdf

    conf = {
        "fugue.serve.state_path": tmp + "/state",
        "fugue.serve.breaker.threshold": 0,
    }
    q = "SELECT k, s, c FROM sess ORDER BY k LIMIT 100"
    frames = []
    fold_secs = 0.0
    freshness: list = []
    with ServeDaemon(conf) as daemon:
        c = ServeClient(*daemon.address, timeout=600)
        sid = c.create_session()
        # wave 0 rides the registration step (compile + first fold,
        # reported separately as the cold share)
        frames.append(land(0))
        t0 = time.perf_counter()
        rep = c.register_pipeline(
            sid,
            {
                "name": "sess",
                "source": src,
                "keys": ["k"],
                "aggs": [["s", "sum", "v"], ["c", "count", "v"]],
                # one uniform host chunk per wave: every fold shares one
                # padded row bucket, so the zero-recompile counter
                # contract is measurable (pyarrow's default batching
                # would tail each file with a ragged second shape)
                "batch_rows": rows_per_wave,
            },
        )["report"]
        c.sql(sid, q)  # view queryable; warms the query programs too
        out["first_batch_secs"] = round(time.perf_counter() - t0, 4)
        for i in range(1, waves):
            frames.append(land(i))
            t_land = time.perf_counter()
            rep = c.step_pipeline(sid, "sess")
            r = c.sql(sid, q)
            freshness.append(time.perf_counter() - t_land)
            fold_secs += rep["secs"]
            assert rep["files"] == 1 and rep["refreshed"], rep
        snap = c.pipeline(sid, "sess")
        agg_stats = snap["aggregator"]
        # exact parity with the one-shot batch run over the file union
        exp = (
            pd.concat(frames).groupby("k")["v"]
            .agg(["sum", "count"]).reset_index()
        )
        got = pd.DataFrame(r["result"]["rows"], columns=["k", "s", "c"])
        parity = bool(
            np.allclose(got["s"].to_numpy(), exp["sum"].to_numpy())
            and (got["c"].to_numpy() == exp["count"].to_numpy()).all()
        )
    warm_rows = rows_per_wave * (waves - 1)
    out["micro_batches"] = snap["progress"]["batches"]
    out["rows_total"] = agg_stats["rows"]
    out["fold_rows_per_sec"] = (
        round(warm_rows / fold_secs, 1) if fold_secs > 0 else 0.0
    )
    out["freshness_secs"] = {
        "p50": round(float(np.percentile(freshness, 50)), 4),
        "max": round(float(np.max(freshness)), 4),
    }
    out["xla_traces"] = agg_stats["traces"]
    out["zero_recompiles_after_first_batch"] = agg_stats["traces"] == 1
    out["batch_parity"] = parity
    return out


def _config11_lake() -> Dict[str, Any]:
    """Versioned table storage (ISSUE 17): optimistic-CAS commit
    throughput under k concurrent writers (with the conflict-retry rate
    the jittered backoff produces), the manifest-stats file-prune ratio
    of a selective scan vs the footer-only baseline (every file opened),
    and the scan speedup compaction buys on a many-small-files table."""
    import tempfile
    import threading

    import numpy as np
    import pandas as pd
    import pyarrow as _pa

    from fugue_tpu.lake import LakeTable

    tmp = tempfile.mkdtemp(prefix="fugue_lake_bench_")
    conf = {"fugue.lake.commit.backoff": 0.002,
            "fugue.lake.commit.retries": 200}
    out: Dict[str, Any] = {}

    # -- commit throughput under k racing writers --------------------------
    k_writers, per_writer = 4, 8
    rows_per_commit = _scale(20_000)
    rng = np.random.default_rng(17)

    def batch(w: int, b: int) -> _pa.Table:
        return _pa.Table.from_pandas(
            pd.DataFrame(
                {
                    "w": np.full(rows_per_commit, w, dtype=np.int64),
                    "t": np.arange(rows_per_commit, dtype=np.int64)
                    + b * rows_per_commit,
                    "v": rng.random(rows_per_commit),
                }
            ),
            preserve_index=False,
        )

    tables = [LakeTable(tmp + "/commits", conf=conf)
              for _ in range(k_writers)]

    def writer(i: int) -> None:
        for b in range(per_writer):
            tables[i].append(batch(i, b))

    threads = [
        threading.Thread(target=writer, args=(i,)) for i in range(k_writers)
    ]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    commit_secs = time.perf_counter() - t0
    commits = sum(t.counters["commits"] for t in tables)
    conflicts = sum(t.counters["conflicts"] for t in tables)
    head = LakeTable(tmp + "/commits")
    assert head.current_version() == k_writers * per_writer
    assert head.read_manifest(head.current_version()).rows == (
        k_writers * per_writer * rows_per_commit
    )
    out["commit"] = {
        "writers": k_writers,
        "commits": commits,
        "commits_per_sec": round(commits / commit_secs, 2),
        "conflict_retries": conflicts,
        "conflict_retry_rate": round(conflicts / commits, 3),
    }

    # -- manifest-stats file pruning vs footer-only ------------------------
    # files are range-partitioned on t by construction (each commit owns
    # a distinct t window), so a selective window predicate can prune
    # whole files from the manifest without touching a parquet footer
    lo = (per_writer - 1) * rows_per_commit  # only the LAST window
    triples = [["t", ">=", lo]]
    probe = LakeTable(tmp + "/commits")
    probe.scan(pruning=triples)  # ONE scan: per-scan prune counters
    scan_t = _timed(lambda: head.scan(pruning=triples), warm=1)
    footer = LakeTable(tmp + "/commits")
    full_t = _timed(lambda: footer.scan(), warm=1)
    total_files = len(head.read_manifest(head.current_version()).files)
    out["pruning"] = {
        "files_total": total_files,
        "files_pruned": probe.counters["files_pruned"],
        "prune_ratio": round(
            probe.counters["files_pruned"] / total_files, 3
        ),
        "pruned_scan_secs": round(scan_t, 4),
        "footer_only_scan_secs": round(full_t, 4),
        "speedup": round(full_t / scan_t, 2) if scan_t > 0 else 0.0,
    }

    # -- compaction scan speedup -------------------------------------------
    frag = LakeTable(tmp + "/frag", conf=conf)
    small_files, small_rows = 64, _scale(10_000) // 8
    for i in range(small_files):
        frag.append(
            _pa.table({"k": np.full(small_rows, i, dtype=np.int64),
                       "v": rng.random(small_rows)})
        )
    before = _timed(lambda: LakeTable(tmp + "/frag").scan(), warm=1)
    m = frag.compact(target_rows=small_files * small_rows)
    after = _timed(lambda: LakeTable(tmp + "/frag").scan(), warm=1)
    out["compaction"] = {
        "files_before": small_files,
        "files_after": len(m.files),
        "scan_secs_before": round(before, 4),
        "scan_secs_after": round(after, 4),
        "speedup": round(before / after, 2) if after > 0 else 0.0,
    }
    return out


def _config12_overload() -> Dict[str, Any]:
    """Overload survival (ISSUE 18): a heavy-tailed query mix (90%
    cheap / 10% heavy, a priority submission every 10th) offered through
    a diurnal arrival ramp at 1x and then 2x worker count, against the
    PREDICTIVE scheduler. The 2x phase runs with an admission wait
    budget derived from the 1x calibration (3x its p99 — the acceptance
    bound itself), so overload SHEDS low-priority arrivals with a
    drain-sized Retry-After instead of letting accepted latency grow
    without bound. Reports p50/p99 of ACCEPTED work at both rates, the
    shed vs lost split (accepted work must NEVER be lost: lost == 0 at
    both rates), the continuous plane riding through the storm
    (standing-pipeline folds and lake CAS commits, all landed), and an
    autoscale up->down cycle with a HARD KILL at the ``serve.scale``
    fault site (zero session loss)."""
    import math
    import os as _os
    import tempfile
    import threading as _threading

    import numpy as np
    import pandas as pd
    import pyarrow as _pa
    import pyarrow.parquet as _pq

    from fugue_tpu.lake import LakeTable
    from fugue_tpu.serve import (
        ServeAPIError,
        ServeClient,
        ServeDaemon,
        ServeFleet,
    )
    from fugue_tpu.testing.faults import FaultPlan, FaultSpec, inject_faults

    sessions = 4
    queries_per_worker = 12
    rows = _scale(200_000)
    cheap_sql = "SELECT k, SUM(v) AS s FROM t GROUP BY k"
    heavy_sql = (
        "SELECT k, SUM(v) AS s, COUNT(*) AS c, MAX(v) AS hi, "
        "MIN(v) AS lo, AVG(v) AS av FROM t GROUP BY k"
    )
    out: Dict[str, Any] = {
        "scheduler": "predictive",
        "sessions": sessions,
        "queries_per_worker": queries_per_worker,
        "rows_per_table": rows,
        "mix": {"heavy_fraction": 0.1, "priority_every": 10},
    }

    def _daemon_conf(tmp: str, max_wait: float) -> Dict[str, Any]:
        return {
            "fugue.serve.scheduler": "predictive",
            "fugue.serve.state_path": tmp + "/state",
            "fugue.serve.max_concurrent": sessions,
            "fugue.serve.breaker.threshold": 0,
            # execution, not cache reads (config 6 idiom): a result hit
            # would collapse the repeated mix into no load at all
            "fugue.serve.result_cache": False,
            "fugue.serve.admission.max_predicted_wait": max_wait,
        }

    def _offered_phase(
        workers_per_session: int, max_wait: float
    ) -> Dict[str, Any]:
        tmp = tempfile.mkdtemp(prefix="fugue_overload_bench_")
        latencies: list = []
        shed: list = []
        lost: list = []
        errors: list = []
        lock = _threading.Lock()
        with ServeDaemon(_daemon_conf(tmp, max_wait)) as daemon:
            host, port = daemon.address
            rng = np.random.default_rng(18)
            handles = []
            for _ in range(sessions):
                # shed must SURFACE (503 + Retry-After), not vanish into
                # the client's transparent retry loop: retries=0
                c = ServeClient(host, port, retries=0, timeout=600)
                sid = c.create_session()
                pdf = pd.DataFrame(
                    {
                        "k": rng.integers(0, 64, rows).astype(np.int64),
                        "v": rng.random(rows),
                    }
                )
                daemon.sessions.get(sid).save_table(
                    "t", daemon.engine.to_df(pdf)
                )
                # warm BOTH tails' programs and seed the cost history
                c.sql(sid, cheap_sql)
                c.sql(sid, heavy_sql)
                handles.append((c, sid))

            def worker(c: Any, sid: str, seed: int) -> None:
                wrng = np.random.default_rng(seed)
                mine = []
                for i in range(queries_per_worker):
                    # diurnal ramp: quiet -> peak (no gap) -> quiet
                    time.sleep(
                        0.04
                        * (1 + math.cos(2 * math.pi * i / queries_per_worker))
                        / 2
                    )
                    sql = (
                        heavy_sql if wrng.random() < 0.1 else cheap_sql
                    )
                    prio = 100 if i % 10 == 0 else 0
                    t0 = time.perf_counter()
                    try:
                        jid = c.submit_async(
                            sid, sql, priority=prio, collect=False
                        )
                    except ServeAPIError as ex:
                        if ex.status == 503:
                            with lock:
                                shed.append(sid)
                            continue
                        with lock:
                            errors.append(repr(ex))
                        continue
                    # accepted work is COMMITTED: it must complete
                    try:
                        r = c.wait(jid)
                        mine.append((time.perf_counter() - t0) * 1000.0)
                        if r["status"] != "done":
                            with lock:
                                lost.append(r.get("error"))
                    except Exception as ex:  # pragma: no cover - in json
                        with lock:
                            lost.append(repr(ex))
                with lock:
                    latencies.extend(mine)

            threads = [
                _threading.Thread(target=worker, args=(c, sid, 100 + j))
                for j, (c, sid) in enumerate(handles)
                for _ in range(workers_per_session)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            rej = daemon.status()["backpressure"]["rejections"]
        offered = sessions * workers_per_session * queries_per_worker
        res: Dict[str, Any] = {
            "workers": sessions * workers_per_session,
            "offered": offered,
            "accepted": len(latencies),
            "shed": len(shed),
            "shed_counted_by_daemon": rej.get("shed", 0),
            "lost": len(lost),
            "errors": errors,
            "wall_secs": round(wall, 4),
            "wait_budget_secs": max_wait,
        }
        if latencies:
            res["p50_ms"] = round(float(np.percentile(latencies, 50)), 2)
            res["p99_ms"] = round(float(np.percentile(latencies, 99)), 2)
        return res

    # 1x calibration: one worker per session, an effectively-unbounded
    # wait budget — nothing sheds, p99 is the baseline
    rate_1x = _offered_phase(1, 600.0)
    out["rate_1x"] = rate_1x
    p99_1x_secs = rate_1x.get("p99_ms", 1000.0) / 1000.0
    # 2x overload: double the workers, and bound accepted wait at 3x the
    # calibrated p99 (the acceptance bound) so excess arrivals shed
    budget = max(0.1, round(3.0 * p99_1x_secs, 3))
    rate_2x = _offered_phase(2, budget)
    out["rate_2x"] = rate_2x
    if "p99_ms" in rate_1x and "p99_ms" in rate_2x:
        ratio = rate_2x["p99_ms"] / max(rate_1x["p99_ms"], 1e-9)
        out["p99_ratio_2x_over_1x"] = round(ratio, 2)
        out["accepted_p99_within_3x"] = bool(ratio <= 3.0)
    out["zero_accepted_lost"] = (
        rate_1x["lost"] == 0 and rate_2x["lost"] == 0
    )

    # -- the continuous plane through the storm ----------------------------
    # a standing pipeline folding waves and a lake table taking CAS
    # commits while a 2x burst saturates the SAME process: overload may
    # shed interactive arrivals, but committed continuous work lands
    def _continuous_block() -> Dict[str, Any]:
        tmp = tempfile.mkdtemp(prefix="fugue_overload_cont_")
        src = _os.path.join(tmp, "in")
        _os.makedirs(src)
        rng = np.random.default_rng(19)
        waves = 6
        rows_per_wave = _scale(20_000)

        def land(i: int) -> None:
            pdf = pd.DataFrame(
                {
                    "k": rng.integers(0, 8, rows_per_wave).astype(np.int64),
                    "v": rng.random(rows_per_wave),
                }
            )
            t = _os.path.join(src, f".w{i}.tmp")
            _pq.write_table(
                _pa.Table.from_pandas(pdf, preserve_index=False), t
            )
            _os.replace(t, _os.path.join(src, f"w{i}.parquet"))

        lake = LakeTable(tmp + "/lake", conf={
            "fugue.lake.commit.backoff": 0.002,
            "fugue.lake.commit.retries": 200,
        })
        commits_tried = 0
        with ServeDaemon(_daemon_conf(tmp, 0.5)) as daemon:
            host, port = daemon.address
            c = ServeClient(host, port, retries=0, timeout=600)
            sid = c.create_session()
            pdf = pd.DataFrame(
                {
                    "k": rng.integers(0, 64, rows).astype(np.int64),
                    "v": rng.random(rows),
                }
            )
            daemon.sessions.get(sid).save_table(
                "t", daemon.engine.to_df(pdf)
            )
            c.sql(sid, cheap_sql)
            land(0)
            c.register_pipeline(
                sid,
                {
                    "name": "sess",
                    "source": src,
                    "keys": ["k"],
                    "aggs": [["s", "sum", "v"], ["c", "count", "v"]],
                    "batch_rows": rows_per_wave,
                },
            )
            shed_local: list = []
            stop = _threading.Event()

            def storm() -> None:
                while not stop.is_set():
                    try:
                        jid = c.submit_async(sid, cheap_sql, collect=False)
                        c.wait(jid)
                    except ServeAPIError as ex:
                        if ex.status != 503:
                            raise
                        shed_local.append(1)
                        time.sleep(0.01)

            stormers = [
                _threading.Thread(target=storm) for _ in range(sessions)
            ]
            for t in stormers:
                t.start()
            fold_errors: list = []
            try:
                for i in range(1, waves):
                    land(i)
                    rep = c.step_pipeline(sid, "sess")
                    if not (rep["files"] == 1 and rep["refreshed"]):
                        fold_errors.append(rep)
                    commits_tried += 1
                    lake.append(
                        _pa.table(
                            {
                                "w": np.full(1000, i, dtype=np.int64),
                                "v": rng.random(1000),
                            }
                        )
                    )
            finally:
                stop.set()
                for t in stormers:
                    t.join(timeout=60)
            snap = c.pipeline(sid, "sess")
        folds = snap["progress"]["batches"]
        return {
            "waves_landed": waves,
            "pipeline_folds": folds,
            "folds_lost": waves - folds,
            "fold_errors": fold_errors,
            "lake_commits": lake.counters["commits"],
            "commits_lost": commits_tried - lake.current_version(),
            "interactive_shed_during_storm": len(shed_local),
        }

    out["continuous_through_storm"] = _continuous_block()

    # -- autoscale cycle with a hard kill at serve.scale -------------------
    def _autoscale_block() -> Dict[str, Any]:
        tmp = tempfile.mkdtemp(prefix="fugue_overload_scale_")
        conf = {
            "fugue.serve.state_path": tmp + "/state",
            "fugue.serve.max_concurrent": 1,
            "fugue.serve.breaker.threshold": 0,
            "fugue.serve.result_cache": False,
            "fugue.serve.fleet.health_interval": 0.05,
            "fugue.serve.fleet.death_threshold": 1,
            # the controller thread is parked (interval=60): the bench
            # drives tick() deterministically, like the chaos tests
            "fugue.serve.autoscale.max_replicas": 2,
            "fugue.serve.autoscale.interval": 60.0,
            "fugue.serve.autoscale.scale_up_queue": 1,
            "fugue.serve.autoscale.sustain_ticks": 1,
            "fugue.serve.autoscale.idle_ticks": 1,
            "fugue.serve.autoscale.cooldown": 0.0,
        }
        res: Dict[str, Any] = {}
        with ServeFleet(conf, replicas=1) as fleet:
            scaler = fleet.autoscaler
            c = ServeClient([fleet.address], retries=10, timeout=600)
            sid0 = c.create_session()
            c.sql(
                sid0,
                "CREATE [[0,1],[0,2],[1,3]] SCHEMA k:long,v:long",
                save_as="t",
                collect=False,
            )
            agg = "SELECT k, SUM(v) AS s FROM t GROUP BY k"
            c.sql(sid0, agg)
            # pressure: async bursts until a tick catches the queue deep
            # enough to add hardware
            t0 = time.perf_counter()
            jids: list = []
            decision = ""
            for _ in range(40):
                jids.extend(
                    c.submit_async(sid0, agg, collect=False)
                    for _ in range(8)
                )
                decision = scaler.tick()
                if decision.startswith("scale_up"):
                    break
            res["scaled_up"] = decision.startswith("scale_up")
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if fleet.router.check_health().get("r1") == "healthy":
                    break
                time.sleep(0.05)
            res["scale_up_secs"] = round(time.perf_counter() - t0, 4)
            for jid in jids:
                c.wait(jid)
            # a fresh session lands on the new replica, then a HARD KILL
            # mid-scale-down degrades to an ordinary death failover
            sid1 = c.create_session()
            c.sql(
                sid1,
                "CREATE [[0,1],[0,2],[1,3]] SCHEMA k:long,v:long",
                save_as="t",
                collect=False,
            )
            victim_rid = fleet.router.affinity()[sid1]
            res["victim_replica"] = victim_rid
            plan = FaultPlan(
                FaultSpec(
                    "serve.scale", f"down {victim_rid}", times=1,
                    error=lambda: OSError("injected kill mid-scale-down"),
                ),
                seed=12,
            )
            t0 = time.perf_counter()
            try:
                with inject_faults(plan):
                    fleet.retire_replica(victim_rid)
                res["hard_kill_injected"] = False
            except OSError:
                res["hard_kill_injected"] = True
            survivor = next(
                r for r in fleet.replica_ids if r != victim_rid
            )
            deadline = time.monotonic() + 30
            adopted = False
            while time.monotonic() < deadline:
                if fleet.router.affinity().get(sid1) == survivor:
                    adopted = True
                    break
                time.sleep(0.05)
            res["adoption_secs"] = round(time.perf_counter() - t0, 4)
            r = c.sql(sid1, agg)
            res["sessions_lost"] = 0 if (
                adopted
                and r["status"] == "done"
                and sorted(r["result"]["rows"]) == [[0, 3], [1, 3]]
            ) else 1
            # the retry of the retire completes the cycle cleanly
            fleet.retire_replica(victim_rid)
            res["replicas_after_cycle"] = len(fleet.replica_ids)
            d = scaler.describe()
            res["scale_ups"] = d["scale_ups"]
        return res

    out["autoscale_cycle"] = _autoscale_block()
    return out


_DEVICE_LOSS_SCRIPT = r"""
import json, sys, time
rows = int(sys.argv[1])
import numpy as np
import pandas as pd
import jax

assert len(jax.devices()) == 4, jax.devices()
from fugue_tpu.column import col
from fugue_tpu.column import functions as ff
from fugue_tpu.jax_backend import JaxExecutionEngine
from fugue_tpu.testing.faults import (
    FaultPlan, FaultSpec, device_lost, inject_faults,
)
from fugue_tpu.workflow import FugueWorkflow

CONF = {
    "fugue.workflow.retry.max_attempts": 3,
    "fugue.workflow.retry.backoff": 0.0,
    "fugue.workflow.retry.jitter": 0.0,
}
rng = np.random.default_rng(13)
left = pd.DataFrame({
    "k": rng.integers(0, 128, rows).astype(np.int64),
    "v": rng.random(rows),
})
right = pd.DataFrame({
    "k": rng.integers(0, 128, rows // 4).astype(np.int64),
    "w": rng.integers(0, 100, rows // 4).astype(np.int64),
})

def build():
    dag = FugueWorkflow()
    j = dag.df(left).inner_join(dag.df(right), on=["k"])
    j.partition_by("k").aggregate(
        total=ff.sum(col("v")), mx=ff.max(col("w"))
    ).yield_dataframe_as("res", as_local=True)
    return dag

def rows_of(res):
    return sorted(
        tuple(round(x, 9) if isinstance(x, float) else x for x in r)
        for r in res["res"].as_array()
    )

e0 = JaxExecutionEngine(dict(CONF))
build().run(e0)  # compile warm-up: the chaos delta measures recovery
t0 = time.perf_counter()
expected = rows_of(build().run(e0))
baseline = time.perf_counter() - t0
e0.stop()

e = JaxExecutionEngine(dict(CONF))
build().run(e)
# time-to-recovery = the degraded-mesh rebuild window itself (retire
# pools, remake mesh, evacuate/re-materialize live frames), measured
# around the engine's recovery hook
rec = {"secs": 0.0}
_real = e.recover_from_device_loss
def timed(ex):
    r0 = time.perf_counter()
    ok = _real(ex)
    rec["secs"] += time.perf_counter() - r0
    return ok
e.recover_from_device_loss = timed
plan = FaultPlan(
    FaultSpec("task", "RunJoin*", times=1, error=lambda: device_lost(1)),
    seed=13,
)
t0 = time.perf_counter()
with inject_faults(plan):
    res = build().run(e)
chaos = time.perf_counter() - t0
got = rows_of(res)
t0 = time.perf_counter()
degraded_again = rows_of(build().run(e)) == expected
degraded_secs = time.perf_counter() - t0
print(json.dumps({
    "devices": 4,
    "rows": rows,
    "baseline_secs": round(baseline, 4),
    "chaos_secs": round(chaos, 4),
    "time_to_recovery_secs": round(rec["secs"], 4),
    "device_recoveries": int(e.device_recoveries),
    "survivors": int(e.surviving_device_count),
    # exact aggregate parity through the loss AND on the degraded
    # 3-device mesh afterwards = zero lost committed work
    "zero_lost_committed_work": bool(got == expected and degraded_again),
    "degraded_followup_secs": round(degraded_secs, 4),
}))
e.stop()
"""


_DEVICE_LOSS_FLEET_SCRIPT = r"""
import json, tempfile, time
import jax

assert len(jax.devices()) == 4, jax.devices()
from fugue_tpu.serve import ServeClient, ServeFleet
from fugue_tpu.testing.faults import device_lost

tmp = tempfile.mkdtemp(prefix="fugue_device_loss_fleet_")
conf = {
    "fugue.serve.state_path": tmp + "/state",
    "fugue.serve.max_concurrent": 2,
    "fugue.serve.breaker.threshold": 0,
    "fugue.serve.result_cache": False,
    "fugue.serve.fleet.health_interval": 0.05,
    "fugue.serve.fleet.death_threshold": 1,
    # parked controller (interval=60): tick() driven deterministically
    "fugue.serve.autoscale.max_replicas": 2,
    "fugue.serve.autoscale.interval": 60.0,
    "fugue.serve.autoscale.scale_up_queue": 2,
    "fugue.serve.autoscale.sustain_ticks": 2,
    "fugue.serve.autoscale.idle_ticks": 2,
    "fugue.serve.autoscale.cooldown": 0.0,
}
agg = "SELECT k, SUM(v) AS s FROM t GROUP BY k"
out = {}
with ServeFleet(conf, replicas=1) as fleet:
    scaler = fleet.autoscaler
    c = ServeClient([fleet.address], retries=10, timeout=600)
    sid = c.create_session()
    c.sql(
        sid, "CREATE [[0,1],[0,2],[1,3]] SCHEMA k:long,v:long",
        save_as="t", collect=False,
    )
    # a device dies under r0: its engine rebuilds onto the survivors
    # and /v1/health flips to "degraded"
    t0 = time.perf_counter()
    assert fleet.replica("r0")._engine.recover_from_device_loss(
        device_lost(2)
    )
    out["recover_secs"] = round(time.perf_counter() - t0, 4)
    # degraded = sustained pressure: first tick spawns the healthy
    # replacement, next tick drain-retires the reduced-mesh replica
    t0 = time.perf_counter()
    d1 = scaler.tick()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if fleet.router.check_health().get("r1") == "healthy":
            break
        time.sleep(0.05)
    out["replace_secs"] = round(time.perf_counter() - t0, 4)
    t0 = time.perf_counter()
    d2 = scaler.tick()
    out["retire_secs"] = round(time.perf_counter() - t0, 4)
    out["decisions"] = [d1, d2]
    r = c.sql(sid, agg)
    out["sessions_lost"] = 0 if (
        fleet.router.affinity().get(sid) == "r1"
        and r["status"] == "done"
        and sorted(r["result"]["rows"]) == [[0, 3], [1, 3]]
        and "t" in c.session(sid)["tables"]
    ) else 1
    out["replicas_after"] = list(fleet.replica_ids)
print(json.dumps(out))
"""


def _config13_device_loss() -> Dict[str, Any]:
    """Device-fault resilience (ISSUE 19): a fresh 4-device process
    loses one device mid shuffle-join (seeded chaos at the ``task``
    site) and the query completes on the 3 survivors with exact
    aggregate parity — reporting ``time_to_recovery_secs`` (the
    degraded-mesh rebuild window), the chaos-vs-baseline wall-clock
    delta, and ``zero_lost_committed_work``. The fleet leg degrades a
    replica's engine the same way and measures the autoscaler's
    replace-then-retire cycle (spawn healthy, drain-retire degraded)
    with ``sessions_lost == 0``."""
    import subprocess
    import sys as _sys

    rows = _scale(200_000)

    def run(script: str, args: list) -> Dict[str, Any]:
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        flags = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")
        ]
        flags.append("--xla_force_host_platform_device_count=4")
        env["XLA_FLAGS"] = " ".join(flags)
        out = subprocess.run(
            [_sys.executable, "-c", script] + args,
            capture_output=True, text=True, timeout=900, env=env,
        )
        if out.returncode != 0:  # surfaced in the artifact, not fatal
            return {"error": out.stderr[-1500:]}
        return json.loads(out.stdout.strip().splitlines()[-1])

    return {
        "query_recovery": run(_DEVICE_LOSS_SCRIPT, [str(rows)]),
        "fleet_failover": run(_DEVICE_LOSS_FLEET_SCRIPT, []),
    }


def _bench() -> Dict[str, Any]:
    headline = _bench_headline()
    configs = {
        "1_map_letter_to_food": _config1_map_letter_to_food(),
        "2_partition_udf": _config2_partition_udf(),
        "3_fuguesql_groupby": _config3_fuguesql_groupby(),
        "3b_sql_join": _config3b_sql_join(),
        "4_cotransform": _config4_cotransform(),
        "5_e2e_parquet": _config5_e2e_parquet(),
        "6_serving_daemon": _config6_serving_daemon(),
        "7_cold_start": _config7_cold_start(),
        "8_serving_fleet": _config8_serving_fleet(),
        "9_continuous": _config9_continuous(),
        "10_scaling": _config10_scaling(),
        "11_lake": _config11_lake(),
        "12_overload": _config12_overload(),
        "13_device_loss": _config13_device_loss(),
    }
    headline["detail"]["configs"] = configs
    # the scaling curve's summary rides the headline contract: devices
    # is already in detail (the headline engine's mesh), the measured
    # multi-device efficiency joins it here
    scaling = configs["10_scaling"]
    headline["detail"]["parallel_efficiency"] = scaling.get(
        "parallel_efficiency", {}
    )
    return headline


if __name__ == "__main__":
    from fugue_tpu.optimize.exec_cache import place_jax_compile_cache

    place_jax_compile_cache(_CHECKOUT)
    res = _bench()
    print(json.dumps(res))  # line 1 = the driver contract
    if os.environ.get("BENCH_CONFIGS", "") == "lines":
        for name, cfg in res["detail"]["configs"].items():
            print(json.dumps({"metric": name, **cfg}))
    # ... and AGAIN as the last line: the driver stores only the output
    # tail, so the artifact must be self-contained
    print(json.dumps(res))
