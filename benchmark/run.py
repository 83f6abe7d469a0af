"""One run of one benchmark cell on the chip.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names a
configuration (``configs/<config>.json``: tables, scale, engine confs and
the ``system`` that serves them, ``drivers/<system>.py``) and a traffic mix
(``traffic/<mix>.json``, read by ``loadgen.py``); each table has a seeded
generator (``gen/<table>.py``), each query a template, its qgen parameters
and a plain reference (``queries/<q>.sql``, ``queries/<q>.py``,
``reference/<q>.py``), each metric a reader (``metrics/<metric>.py``).

A run generates the data from ``--seed``, ingests it, warms every query
text the traffic will send (set-up), then runs the traffic's closed-loop
streams: queries start while the clock is under ``--seconds`` and the
window closes when the last started one completes. Afterwards it checks
that every frame sat on the chip and that the engine fell back to the host
nowhere (else it exits 3), frees the program's state, and compares every
answer with the reference. The last stdout line is one JSON object; its
last key, ``checks``, holds each compared number beside its limit, which
are also the last lines on stderr. With ``--trace 1`` the window runs under
the profiler and the line carries the per-layer metrics and ``breakdown``;
with ``--trace 0`` the end-to-end metrics.

It exits 2, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from typing import Any, Callable, Dict, Iterator, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# device bytes per row of each column dtype in a configuration's schema
# (strings are int32 dictionary codes on the device)
WIDTHS = {"int64": 8, "int32": 4, "float64": 8, "float32": 4, "string": 4}

if __name__ == "__main__" and os.path.abspath(sys.path[0]) == HERE:
    # import the harness as the package ``benchmark`` from the checkout,
    # never its files as top-level modules (``trace`` is also a stdlib name)
    sys.path[0] = CHECKOUT


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


_PLUGINS: Dict[str, Any] = {}


def plugin(kind: str, name: str, root: str = CHECKOUT) -> Any:
    """The module ``<root>/benchmark/<kind>/<name>.py``, loaded by its path."""
    path = os.path.join(root, "benchmark", kind, f"{name}.py")
    if path not in _PLUGINS:
        if not os.path.isfile(path):
            raise LookupError(f"no {kind} named {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(f"benchmark.{kind}.{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)  # type: ignore[union-attr]
        _PLUGINS[path] = mod
    return _PLUGINS[path]


def find_cell(spec: Dict[str, Any], workload: str) -> Dict[str, Any]:
    for cell in spec["workloads"]:
        if cell["name"] == workload:
            return cell
    raise LookupError(f"no workload {workload!r} in BENCHMARK.json")


def cell_metrics(spec: Dict[str, Any], workload: str, trace: bool) -> List[Dict[str, Any]]:
    """The metrics this cell reports: per-layer under ``--trace 1``,
    end-to-end otherwise; a metric with ``workloads`` only in those."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


class CompileCounter:
    """Counts XLA backend compiles (cache loads included) in this process."""

    def __init__(self) -> None:
        self.n = 0
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, **kwargs: Any) -> None:
        if event == BACKEND_COMPILE:
            with self._lock:
                self.n += 1


_COUNTER: Optional[CompileCounter] = None


def compile_counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        import jax

        _COUNTER = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(_COUNTER)
    return _COUNTER


@contextmanager
def annotate(name: str) -> Iterator[None]:
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


class RunContext:
    """What the metric readers see of one run."""

    def __init__(self, **kw: Any):
        self.records: List[Dict[str, Any]] = []
        self.window_s = 0.0
        self.setup_s = 0.0
        self.compiles_in_window = 0
        self.trace: Optional[Dict[str, Any]] = None
        self.counters_before: Dict[str, Any] = {}
        self.counters_after: Dict[str, Any] = {}
        self.query_bytes: Dict[str, float] = {}
        self.peaks: Dict[str, Any] = {}
        self.__dict__.update(kw)

    def peak(self, key: str) -> float:
        return float(self.peaks[key])


def device_peaks(kind: str, root: str = CHECKOUT) -> Dict[str, Any]:
    table = load_json(root, "benchmark", "peaks.json")
    if kind not in table:
        raise LookupError(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


def check_devices(chips: int, require_tpu: bool) -> List[Any]:
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found {devices[0].platform!r} devices, not 'tpu'")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices


def _query_bytes(config: Dict[str, Any], mod: Any) -> float:
    table = config["tables"][mod.TABLE]
    return float(table["rows"]) * sum(WIDTHS[table["schema"][c]] for c in mod.READS)


def _run_streams(streams: List[Any], driver: Any, seconds: float) -> List[Dict[str, Any]]:
    """Closed-loop streams: each sends its next query once the last one
    answered, while the clock is under ``seconds``."""
    records: List[Dict[str, Any]] = []
    lock = threading.Lock()
    t_start = time.perf_counter()
    deadline = t_start + seconds

    def loop(stream: Any) -> None:
        for q in stream:
            t_send = time.perf_counter()
            if t_send >= deadline:
                return
            rec: Dict[str, Any] = {"stream": stream.index, "query": q.name, "key": q.key,
                                   "t_send": t_send - t_start}
            try:
                with annotate(f"bench.query.{q.name}"):
                    rec["answer"] = driver.run(stream.index, q.text)
                rec["ok"] = True
            except Exception as e:  # a failed query is counted, not fatal
                rec["ok"] = False
                rec["error"] = f"{type(e).__name__}: {e}"
            rec["t_done"] = time.perf_counter() - t_start
            with lock:
                records.append(rec)

    if len(streams) == 1:
        loop(streams[0])
    else:
        threads = [threading.Thread(target=loop, args=(s,), daemon=True) for s in streams]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    return records


def judge(checks: Dict[str, Dict[str, Any]]) -> bool:
    """``correct``: every compared number within its limit."""
    return all(
        c["value"] >= c["limit"] if c.get("at_least") else c["value"] <= c["limit"]
        for c in checks.values()
    )


def _checks(queries: Dict[str, Any], pairs: List[Any], failed: int) -> Any:
    """The compared numbers of one set of answers, each with its limit:
    ``pairs`` holds ``(query, answer, reference answer)``. Also returns,
    for each pair, whether that answer alone is wrong."""
    from benchmark.compare import compare

    checks: Dict[str, Dict[str, Any]] = {}
    for name, (mod, _) in queries.items():
        checks[f"{name}.exact_mismatches"] = {"value": 0, "limit": 0}
        checks[f"{name}.max_rel_err"] = {"value": 0.0, "limit": mod.MAX_REL_ERR}
    wrong = []
    for name, got, want in pairs:
        m, w = compare(got, want)
        checks[f"{name}.exact_mismatches"]["value"] += m
        widest = checks[f"{name}.max_rel_err"]
        widest["value"] = max(widest["value"], w)
        wrong.append(m > 0 or w > widest["limit"])
    checks["failed_queries"] = {"value": failed, "limit": 0}
    checks["answers_compared"] = {"value": len(pairs), "limit": 1, "at_least": True}
    return checks, wrong


def _compare_answers(
    queries: Dict[str, Any],
    records: List[Dict[str, Any]],
    streams: List[Any],
    tables: Dict[str, Any],
    root: str,
    control_dtype: Any,
) -> Any:
    """Every answer against the float64 reference for its query and
    parameters: ``(checks, control)``. ``control`` (None without a
    ``control_dtype``) puts the reference computed in that dtype in the
    program's place, one answer per query text, and reduces it by the same
    ``_checks`` and ``judge``: ``{"correct": ..., "checks": ...}``."""
    import numpy as np

    params = {q.key: q.params for s in streams for q in s.queries}
    wants: Dict[Any, Any] = {}

    def want(name: str, key: str) -> Any:
        if (name, key) not in wants:
            mod = queries[name][0]
            wants[name, key] = plugin("reference", name, root).answer(
                tables[mod.TABLE], params[key], np.float64)
        return wants[name, key]

    ok = [r for r in records if r["ok"]]
    pairs = [(r["query"], r["answer"], want(r["query"], r["key"])) for r in ok]
    checks, wrong = _checks(queries, pairs, len(records) - len(ok))
    for r, w in zip(ok, wrong):
        r["wrong"] = w
    if control_dtype is None:
        return checks, None
    lower = []
    for (name, key), ref in wants.items():
        mod = queries[name][0]
        low = plugin("reference", name, root).answer(tables[mod.TABLE], params[key], control_dtype)
        lower.append((name, low, ref))
    control_checks, _ = _checks(queries, lower, 0)
    return checks, {"correct": judge(control_checks), "checks": control_checks}


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool = False,
    require_tpu: bool = True,
    control_dtype: Any = None,
    log: Callable[[str], None] = lambda s: print(s, file=sys.stderr, flush=True),
    out: Callable[[str], None] = lambda s: print(s, flush=True),
    root: str = CHECKOUT,
) -> Dict[str, Any]:
    """Run one cell and return its result line (a dict). ``control_dtype``
    (``readings.py`` and the tests only) also reduces the reference
    computed in that dtype in the program's place, under ``control``."""
    import fugue_tpu  # noqa: F401  (the system under test: fail before any work)
    from benchmark.loadgen import make_streams

    bench = os.path.join(root, "benchmark")
    spec = load_json(root, "BENCHMARK.json")
    cell = find_cell(spec, workload)
    config = load_json(bench, "configs", f"{cell['config']}.json")
    mix = load_json(bench, "traffic", f"{cell['traffic']}.json")
    devices = check_devices(int(cell["chips"]), require_tpu)
    dev = devices[0]
    peaks = device_peaks(dev.device_kind, root) if require_tpu else {}
    counter = compile_counter()
    t_import = time.perf_counter()

    tables = {
        name: plugin("gen", name, root).generate(int(t["rows"]), float(config["scale_factor"]), seed)
        for name, t in config["tables"].items()
    }
    t_gen = time.perf_counter()
    driver = plugin("drivers", config["system"], root).Driver(config, tables, annotate)
    t_ingest = time.perf_counter()
    queries = {}
    for q in mix["queries"]:
        with open(os.path.join(bench, "queries", f"{q}.sql")) as f:
            queries[q] = (plugin("queries", q, root), f.read())
    streams = make_streams(mix, queries, seed)
    texts = {}
    for s in streams:
        for q in s.queries:
            texts[q.text] = q
    for _ in range(2):  # the first compiles or loads the cache, the second is warm
        for text in texts:
            driver.run(0, text)
    t_warm = time.perf_counter()
    compiles_setup = counter.n
    in_bytes = sum(t.nbytes for t in tables.values())
    out(json.dumps({"setup": {
        "import_init_s": t_import - _T0,
        "generate_s": t_gen - t_import,
        "ingest_s": t_ingest - t_gen,
        "ingest_gb_per_s": in_bytes / 1e9 / max(t_ingest - t_gen, 1e-9),
        "warm_s": t_warm - t_ingest,
        "query_texts": len(texts),
        "compiles_in_setup": compiles_setup,
    }}))

    ctx = RunContext(
        query_bytes={q: _query_bytes(config, mod) for q, (mod, _) in queries.items()},
        peaks=peaks,
    )
    rows = {q: int(config["tables"][mod.TABLE]["rows"]) for q, (mod, _) in queries.items()}
    ctx.counters_before = driver.counters()
    trace_dir = os.path.join(root, ".bench_trace")
    if trace:
        import jax

        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0 = counter.n
    t_window = time.perf_counter()
    ctx.setup_s = t_window - _T0
    with annotate("bench.window"):
        records = _run_streams(streams, driver, seconds)
    ctx.compiles_in_window = counter.n - c0
    if trace:
        import jax

        jax.profiler.stop_trace()
    ctx.counters_after = driver.counters()
    ctx.window_s = max((r["t_done"] for r in records), default=0.0)
    for r in records:
        r["rows"] = rows[r["query"]]
    ctx.records = records
    out(json.dumps({"window": {
        "params": {s.index: [q.key for q in s.queries] for s in streams},
        "query_s": [[r["query"], r["t_send"], r["t_done"] - r["t_send"]] for r in records],
    }}))
    used = devices[: int(cell["chips"])]
    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in used)
    driver.check(dev.platform)
    out(json.dumps({"counters": {
        "before": ctx.counters_before,
        "after": ctx.counters_after,
        "compiles_in_window": ctx.compiles_in_window,
        "peak_bytes_in_use": peak_bytes,
    }}))
    driver.close()
    del driver

    # the comparison, once the program's state is freed
    checks, control = _compare_answers(queries, records, streams, tables, root, control_dtype)
    errors = [r for r in records if not r["ok"]]
    if errors:
        log(f"{len(errors)} queries failed; the first: {errors[0]['error']}")
    correct = judge(checks)

    if trace:
        from benchmark.trace import reduce

        xplanes = []
        for d, _, files in os.walk(trace_dir):
            xplanes += [os.path.join(d, f) for f in files if f.endswith(".xplane.pb")]
        if len(xplanes) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {xplanes}")
        ctx.trace = reduce(xplanes[0])

    metrics = {}
    for m in cell_metrics(spec, workload, trace):
        value = plugin("metrics", m["name"], root).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device: Dict[str, Any] = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak_bytes,
    }
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": len(records),
        "failed": sum(1 for r in records if not r["ok"] or r.get("wrong")),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {
            "device_ops": ctx.trace["device_ops"],
            "idle_gaps": ctx.trace["idle_gaps"],
        }
    if control_dtype is not None:
        result["control"] = control
    result["checks"] = checks
    return result


def _place_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), for every program."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        jax.config.update("jax_compilation_cache_dir", os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv: List[str]) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    from benchmark.checks import BenchFault

    _place_compile_cache()
    try:
        result = run_cell(args.workload, args.seed, args.seconds, trace=bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}; refusing to run", file=sys.stderr)
        return 2
    except BenchFault as e:
        print(f"run.py: not a chip measurement: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
