"""Run the jax engine's main path once on a TPU chip and prove it ran there.

One process holds the chip for its whole life (a child could not take it).
Every phase pins the engine to the device tier (``fugue.jax.placement=
device``) and, by default, to one device (``fugue.jax.devices=0``), so the
answers are the same on a one-chip and a four-chip host:

- (a) headline: ``transform()`` with a jax-annotated transformer, then a
  sum/count/mean group-by over 100M rows (int64 key, 1,024 values; float64
  value), checked against ``np.bincount`` on the same arrays;
- (b) FugueSQL over parquet: LOAD -> JOIN -> GROUP BY -> SAVE over a 20M-row
  fact table and a 1M-row unique-key dim table, checked against
  ``NativeExecutionEngine`` on the same files;
- (c) serve: a ``ServeDaemon`` on the jax engine; through ``ServeClient`` a
  session saves the fact table and answers 3 queries, checked against (b)'s
  native answers;
- (d) float keys: ``-0.0``, ``0.0`` and NaN through distinct and group-by.

``--chips 4`` runs only (a) and (b) with ``fugue.jax.shuffle=on`` on a mesh
over four devices, compares them with the same phases on device 0 in this
process, and checks that the output blocks span four devices.

The script exits non-zero when a result differs, a frame sits on a CPU mesh,
``engine.fallbacks`` is not empty, or JAX finds no TPU. ``--rehearse`` (with
``JAX_PLATFORMS=cpu``) is the only way to run it on the CPU: every size
shrinks 1,000x. The last line of stdout is the contract line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
each phase prints one JSON line before it. Compile and run seconds in those
lines are informational, not a benchmark.
"""

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq

CHECKOUT = os.path.dirname(os.path.abspath(__file__))

# full sizes; --rehearse divides each by REHEARSE_SHRINK
HEADLINE_ROWS = 100_000_000
HEADLINE_GROUPS = 1024
FACT_ROWS = 20_000_000
DIM_ROWS = 1_000_000
DIM_CATS = 100
FLOAT_KEY_ROWS = 1_000_000
REHEARSE_SHRINK = 1000
# float64 sums of up to ~1e5 terms in another order: n * eps is ~1e-11
RTOL = 1e-9


class SmokeError(AssertionError):
    """A phase's result, placement or fallback check failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def _close(name: str, got: Any, want: Any) -> None:
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    _check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    if not np.allclose(got, want, rtol=RTOL, atol=0.0, equal_nan=True):
        bad = int(np.argmax(~np.isclose(got, want, rtol=RTOL, atol=0.0)))
        raise SmokeError(f"{name}: row {bad} got {got[bad]!r}, want {want[bad]!r}")


def _equal_frames(name: str, got: pd.DataFrame, want: pd.DataFrame, key: str) -> None:
    """Same rows (after sorting by ``key``): integer columns exactly, float
    columns within RTOL."""
    _check(
        sorted(got.columns) == sorted(want.columns),
        f"{name}: columns {list(got.columns)} != {list(want.columns)}",
    )
    got = got.sort_values(key).reset_index(drop=True)
    want = want.sort_values(key).reset_index(drop=True)
    _check(len(got) == len(want), f"{name}: {len(got)} rows != {len(want)}")
    for c in want.columns:
        if pd.api.types.is_float_dtype(want[c]) or pd.api.types.is_float_dtype(got[c]):
            _close(f"{name}.{c}", got[c].to_numpy(), want[c].to_numpy())
        else:
            g, w = got[c].tolist(), want[c].tolist()
            _check(g == w, f"{name}.{c}: {g[:5]}... != {w[:5]}...")


class Phase:
    """Times one phase and checks where its frames live. ``run_s`` is the
    warm repeat's wall clock, ``compile_s`` the first run's wall clock less
    it (tracing and compiling), ``phase_wall_s`` everything from the
    phase's start, data and references included. All are informational."""

    def __init__(self, name: str, engine: Any, platform: str):
        self.name = name
        self.engine = engine
        self.platform = platform
        self.frames: List[Any] = []
        self.rows = 0
        self.cold_s = 0.0
        self.run_s = 0.0
        self._t0 = time.perf_counter()
        self._strategies0 = engine.strategy_counts

    def frame(self, df: Any) -> Any:
        """Record a result frame (a ``JaxDataFrame``) for the placement check."""
        self.frames.append(df)
        return df

    def cold_then_warm(self, fn: Any) -> Any:
        """Run ``fn`` twice: the first run traces and compiles, the second
        is timed as ``run_s``. Returns the second run's result."""
        t0 = time.perf_counter()
        fn()
        t1 = time.perf_counter()
        out = fn()
        self.cold_s, self.run_s = t1 - t0, time.perf_counter() - t1
        return out

    def finish(self, **extra: Any) -> Dict[str, Any]:
        from fugue_tpu.jax_backend.dataframe import JaxDataFrame

        meshes = []
        spans = set()
        for df in self.frames:
            _check(isinstance(df, JaxDataFrame), f"{self.name}: {type(df)} is not a jax frame")
            meshes.append(df.native.mesh)
            spans.update(
                len(c.data.sharding.device_set)
                for c in df.native.columns.values()
                if c.on_device
            )
        meshes.append(self.engine.mesh)
        # every frame the engine still tracks, not only the results
        meshes.extend(b.mesh for b in list(self.engine._live_frames))
        platforms = sorted({d.platform for m in meshes for d in m.devices.flat})
        fallbacks = self.engine.fallbacks
        strategies = {
            k: v - self._strategies0.get(k, 0)
            for k, v in self.engine.strategy_counts.items()
            if v > self._strategies0.get(k, 0)
        }
        dev = self.engine.mesh.devices.flat[0]
        line = {
            "phase": self.name,
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "rows": self.rows,
            "mesh_platforms": platforms,
            "device_spans": sorted(spans),
            "fallbacks": fallbacks,
            "groupby_strategies": strategies,
            "compile_s": self.cold_s - self.run_s,
            "run_s": self.run_s,
            "phase_wall_s": time.perf_counter() - self._t0,
            "timing": "informational, not a benchmark",
            **extra,
        }
        print(json.dumps(line), flush=True)
        _check(platforms == [self.platform], f"{self.name}: frames on {platforms}")
        ndev = int(self.engine.mesh.devices.size)
        _check(spans == {ndev}, f"{self.name}: blocks span {sorted(spans)} devices, not {ndev}")
        _check(not fallbacks, f"{self.name}: engine fell back to the host: {fallbacks}")
        return line


def _engine_conf(devices: str, shuffle: str = "auto") -> Dict[str, Any]:
    return {
        "fugue.jax.devices": devices,
        "fugue.jax.placement": "device",
        "fugue.jax.shuffle": shuffle,
    }


# ---------------------------------------------------------------------------
# (a) transform() + group-by
# ---------------------------------------------------------------------------
def headline_data(seed: int, rows: int) -> pa.Table:
    rng = np.random.default_rng(seed)
    return pa.table(
        {
            "k": rng.integers(0, HEADLINE_GROUPS, rows, dtype=np.int64),
            "v": rng.random(rows),
        }
    )


def phase_headline(engine: Any, data: pa.Table, platform: str, name: str) -> pd.DataFrame:
    import jax

    import fugue_tpu.api as fa
    from fugue_tpu import ArrowDataFrame
    from fugue_tpu.column import col
    from fugue_tpu.column import functions as ff

    def scale(arrs: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        return {"k": arrs["k"], "v2": arrs["v"] * 2.0 + 1.0}

    ph = Phase(name, engine, platform)
    ph.rows = data.num_rows
    src = ph.frame(engine.persist(engine.to_df(ArrowDataFrame(data))))

    def run() -> pd.DataFrame:
        out = ph.frame(
            fa.transform(src, scale, schema="k:long,v2:double", engine=engine, as_fugue=True)
        )
        agg = ph.frame(
            fa.aggregate(
                out, partition_by="k", engine=engine, as_fugue=True,
                s=ff.sum(col("v2")), c=ff.count(col("v2")), m=ff.avg(col("v2")),
            )
        )
        return agg.as_pandas()

    res = ph.cold_then_warm(run)
    ph.finish(groups=len(res))
    return res


def check_headline(res: pd.DataFrame, data: pa.Table) -> None:
    keys = data.column("k").to_numpy()
    v2 = data.column("v").to_numpy() * 2.0 + 1.0
    c = np.bincount(keys, minlength=HEADLINE_GROUPS)
    s = np.bincount(keys, weights=v2, minlength=HEADLINE_GROUPS)
    occupied = np.nonzero(c)[0]
    want = pd.DataFrame(
        {"k": occupied, "s": s[occupied], "c": c[occupied], "m": s[occupied] / c[occupied]}
    )
    _equal_frames("headline", res, want, "k")


# ---------------------------------------------------------------------------
# (b) FugueSQL over parquet, (c) serve
# ---------------------------------------------------------------------------
def write_parquet(data_dir: str, seed: int, fact_rows: int, dim_rows: int) -> Dict[str, str]:
    os.makedirs(data_dir, exist_ok=True)
    rng = np.random.default_rng(seed + 1)
    paths = {
        "fact": os.path.join(data_dir, "fact.parquet"),
        "dim": os.path.join(data_dir, "dim.parquet"),
    }
    pq.write_table(
        pa.table(
            {
                "dk": rng.integers(0, dim_rows, fact_rows, dtype=np.int64),
                "qty": rng.integers(1, 51, fact_rows, dtype=np.int64),
                "price": rng.random(fact_rows) * 100.0,
            }
        ),
        paths["fact"],
    )
    pq.write_table(
        pa.table(
            {
                # sorted and unique: the dim side of a key/foreign-key join
                "dk": np.arange(dim_rows, dtype=np.int64),
                "cat": rng.integers(0, DIM_CATS, dim_rows, dtype=np.int64),
                "weight": rng.random(dim_rows),
            }
        ),
        paths["dim"],
    )
    return paths


def join_sql(fact: str, dim: str) -> str:
    return (
        "SELECT d.cat, SUM(f.price * d.weight) AS amt, COUNT(*) AS n, "
        "AVG(f.qty) AS q, MIN(f.price) AS lo, MAX(f.price) AS hi "
        f"FROM {fact} AS f INNER JOIN {dim} AS d "
        "ON f.dk = d.dk GROUP BY d.cat"
    )


def sql_script(paths: Dict[str, str], out: str) -> str:
    return (
        f'f = LOAD "{paths["fact"]}"\n'
        f'd = LOAD "{paths["dim"]}"\n'
        f"{join_sql('f', 'd')}\n"
        f'SAVE OVERWRITE "{out}"\n'
    )


# the serve phase's queries over the session's saved "fact" table; the
# join's dim side is LOADed per query
def serve_queries(paths: Dict[str, str]) -> Dict[str, str]:
    return {
        "by_qty": "SELECT qty, COUNT(*) AS n, SUM(price) AS s FROM fact GROUP BY qty",
        "filtered": (
            "SELECT COUNT(*) AS n, SUM(price) AS s, MIN(price) AS lo, "
            "MAX(price) AS hi FROM fact WHERE qty > 25"
        ),
        "join": f'd = LOAD "{paths["dim"]}"\n' + join_sql("fact", "d"),
    }


def native_answers(paths: Dict[str, str], out: str) -> Dict[str, pd.DataFrame]:
    from fugue_tpu import NativeExecutionEngine, fugue_sql

    native = NativeExecutionEngine()
    scripts = {"join": sql_script(paths, out)}
    for name, q in serve_queries(paths).items():
        if name != "join":
            scripts[name] = f'fact = LOAD "{paths["fact"]}"\n{q}'
    return {
        name: fugue_sql(s, engine=native, as_fugue=True).as_pandas()
        for name, s in scripts.items()
    }


def phase_sql(engine: Any, paths: Dict[str, str], out: str, platform: str, name: str) -> pd.DataFrame:
    from fugue_tpu import fugue_sql

    ph = Phase(name, engine, platform)
    ph.rows = pq.read_metadata(paths["fact"]).num_rows

    def run() -> pd.DataFrame:
        res = ph.frame(fugue_sql(sql_script(paths, out), engine=engine, as_fugue=True))
        return res.as_pandas()

    res = ph.cold_then_warm(run)
    saved = pd.read_parquet(out)
    _equal_frames(f"{name}.saved", saved, res, "cat")
    ph.finish(groups=len(res))
    return res


def phase_serve(paths: Dict[str, str], native: Dict[str, pd.DataFrame], platform: str) -> None:
    from fugue_tpu.serve import ServeClient, ServeDaemon

    conf = dict(_engine_conf("0"), **{"fugue.serve.result_cache": False})
    with ServeDaemon(conf, engine="jax") as daemon:
        ph = Phase("c_serve", daemon.engine, platform)
        ph.rows = pq.read_metadata(paths["fact"]).num_rows
        client = ServeClient(*daemon.address, timeout=1200)
        sid = client.create_session()
        saved = client.sql(sid, f'LOAD "{paths["fact"]}"', save_as="fact", collect=False)
        _check(saved["status"] == "done", f"c_serve save: {saved.get('error')}")

        def ask() -> Dict[str, pd.DataFrame]:
            out = {}
            for qname, q in serve_queries(paths).items():
                snap = client.sql(sid, q)
                _check(snap["status"] == "done", f"c_serve {qname}: {snap.get('error')}")
                r = snap["result"]
                out[qname] = pd.DataFrame(r["rows"], columns=r["columns"])
            return out

        got = ph.cold_then_warm(ask)
        for qname, want in native.items():
            key = {"by_qty": "qty", "filtered": "n", "join": "cat"}[qname]
            _equal_frames(f"c_serve.{qname}", got[qname], want, key)
        for df in daemon.sessions.get(sid).table_frames().values():
            ph.frame(df)
        client.close_session(sid)
        ph.finish(queries=len(got))


# ---------------------------------------------------------------------------
# (d) float keys
# ---------------------------------------------------------------------------
def phase_float_keys(engine: Any, seed: int, rows: int, platform: str) -> None:
    import fugue_tpu.api as fa
    from fugue_tpu import ArrowDataFrame
    from fugue_tpu.column import col
    from fugue_tpu.column import functions as ff

    ph = Phase("d_float_keys", engine, platform)
    # the exact case: -0.0 groups with 0.0, every NaN lands in one group
    small = engine.to_df(
        pd.DataFrame({"a": [1.5, -0.0, 0.0, np.nan, np.nan], "b": [1, 2, 4, 8, 16]})
    )
    dist = ph.frame(fa.distinct(small, engine=engine, as_fugue=True))
    _check(dist.count() == 5, f"d_float_keys distinct: {dist.as_array()}")
    agg = ph.frame(
        fa.aggregate(small, partition_by="a", engine=engine, as_fugue=True, s=ff.sum(col("b")))
    )
    rows_ = sorted(agg.as_array(), key=str)
    _check(rows_ == [[0.0, 6], [1.5, 1], [None, 24]], f"d_float_keys aggregate: {rows_}")
    # the same keys at size
    rng = np.random.default_rng(seed + 2)
    choices = np.array([-0.0, 0.0, np.nan, 1.5, -2.5])
    a = choices[rng.integers(0, len(choices), rows)]
    b = rng.integers(0, 1000, rows, dtype=np.int64)
    ph.rows = rows
    big = ph.frame(engine.persist(engine.to_df(ArrowDataFrame(pa.table({"a": a, "b": b})))))

    def run() -> pd.DataFrame:
        res = ph.frame(
            fa.aggregate(big, partition_by="a", engine=engine, as_fugue=True, s=ff.sum(col("b")))
        )
        return res.as_pandas()

    res = ph.cold_then_warm(run)
    nan = np.isnan(a)
    zero = a == 0.0
    want = pd.DataFrame(
        {
            "a": [-2.5, 0.0, 1.5, np.nan],
            "s": [int(b[a == -2.5].sum()), int(b[zero].sum()), int(b[a == 1.5].sum()), int(b[nan].sum())],
        }
    )
    got = res.assign(a=res["a"].astype("float64"))
    got = got.sort_values("a", na_position="last").reset_index(drop=True)
    _close("d_float_keys.a", got["a"], want["a"])
    _check(got["s"].tolist() == want["s"].tolist(), f"d_float_keys sums: {got['s'].tolist()}")
    ndist = ph.frame(fa.distinct(fa.select(big, col("a"), engine=engine, as_fugue=True), engine=engine, as_fugue=True))
    _check(ndist.count() == 4, f"d_float_keys distinct keys: {ndist.count()}")
    ph.finish(groups=len(res))


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def run_four_chips(args: argparse.Namespace, sizes: Dict[str, int], platform: str) -> None:
    import jax

    from fugue_tpu import make_execution_engine

    ndev = len(jax.devices())
    _check(ndev >= 4, f"--chips 4 needs four devices, found {ndev}")
    one = make_execution_engine("jax", _engine_conf("0"))
    # Phase.finish checks that every block spans the engine's four devices
    four = make_execution_engine("jax", _engine_conf("0,1,2,3", shuffle="on"))
    data = headline_data(args.seed, sizes["headline"])
    a1 = phase_headline(one, data, platform, "a_headline_1dev")
    a4 = phase_headline(four, data, platform, "a_headline_4dev")
    check_headline(a1, data)
    _equal_frames("a_headline 4 vs 1", a4, a1, "k")
    paths = write_parquet(args.data_dir, args.seed, sizes["fact"], sizes["dim"])
    b1 = phase_sql(one, paths, os.path.join(args.data_dir, "out_1.parquet"), platform, "b_sql_1dev")
    b4 = phase_sql(four, paths, os.path.join(args.data_dir, "out_4.parquet"), platform, "b_sql_4dev")
    _equal_frames("b_sql 4 vs 1", b4, b1, "cat")
    # the GROUP BY's MIN/MAX take the generic aggregate path, which on a
    # multi-device mesh runs the map-side combine (preagg) all-to-all
    shuffled = four.shuffle_counts
    print(json.dumps({"phase": "four_chip_compare", "equal": True, "shuffle_counts": shuffled}), flush=True)
    _check(shuffled.get("aggregate", 0) > 0, f"no shuffled aggregate ran: {shuffled}")


def run_one_chip(args: argparse.Namespace, sizes: Dict[str, int], platform: str) -> None:
    from fugue_tpu import make_execution_engine

    engine = make_execution_engine("jax", _engine_conf("0"))
    data = headline_data(args.seed, sizes["headline"])
    check_headline(phase_headline(engine, data, platform, "a_headline"), data)
    del data
    paths = write_parquet(args.data_dir, args.seed, sizes["fact"], sizes["dim"])
    native = native_answers(paths, os.path.join(args.data_dir, "out_native.parquet"))
    res = phase_sql(engine, paths, os.path.join(args.data_dir, "out_jax.parquet"), platform, "b_sql")
    _equal_frames("b_sql vs native", res, native["join"], "cat")
    phase_serve(paths, native, platform)
    phase_float_keys(engine, args.seed, sizes["float_keys"], platform)


def main(argv: List[str]) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument(
        "--data-dir", default=os.path.join(CHECKOUT, ".smoke_data"),
        help="where the parquet tables are written (default: <checkout>/.smoke_data)",
    )
    p.add_argument(
        "--rehearse", action="store_true",
        help="run on the CPU at 1/1000 of every size (needs JAX_PLATFORMS=cpu)",
    )
    args = p.parse_args(argv)
    if args.rehearse and os.environ.get("JAX_PLATFORMS") != "cpu":
        p.error("--rehearse requires JAX_PLATFORMS=cpu")

    from fugue_tpu.optimize.exec_cache import place_jax_compile_cache

    place_jax_compile_cache(CHECKOUT)
    import jax

    dev = jax.devices()[0]
    platform = "cpu" if args.rehearse else "tpu"
    if dev.platform != platform:
        print(
            f"chip_smoke: JAX found {dev.platform!r} devices, not {platform!r}; "
            "refusing to run (use --rehearse with JAX_PLATFORMS=cpu on the CPU)",
            file=sys.stderr,
        )
        return 1
    shrink = REHEARSE_SHRINK if args.rehearse else 1
    sizes = {
        "headline": HEADLINE_ROWS // shrink,
        "fact": FACT_ROWS // shrink,
        "dim": DIM_ROWS // shrink,
        "float_keys": FLOAT_KEY_ROWS // shrink,
    }
    if args.chips == 4:
        run_four_chips(args, sizes, platform)
    else:
        run_one_chip(args, sizes, platform)
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(jax.devices()),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
