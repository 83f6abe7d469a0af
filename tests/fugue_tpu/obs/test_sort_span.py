"""The ``engine.sort`` span on the profiler's clock: one around each
ORDER BY's device sort and gather, with the mesh's ``devices``, and the
sort program's ``engine.dispatch`` inside it. Tier-1 compatible; select
with ``-m obs``."""

import glob
import os

import jax
import pyarrow as pa
import pytest

pytestmark = pytest.mark.obs


def _record(tmp_path, fn):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                    )
    return events


@pytest.mark.parametrize("devices", ["0", "0,1,2,3"])
def test_sort_span_wraps_the_device_sort(tmp_path, devices):
    from fugue_tpu import ArrowDataFrame, fugue_sql, make_execution_engine

    table = pa.table({
        "k": pa.array([(i * 7919) % 1_000 for i in range(1_000)], pa.int64()),
        "v": pa.array([float(i) for i in range(1_000)]),
    })
    engine = make_execution_engine(
        "jax", {"fugue.jax.placement": "device", "fugue.jax.devices": devices}
    )
    df = engine.persist(engine.to_df(ArrowDataFrame(table)))
    got = {}

    def queries():
        got["sorted"] = fugue_sql("SELECT k, v FROM t WHERE v > 10 ORDER BY k DESC LIMIT 5",
                                  t=df, engine=engine, as_fugue=True).as_pandas()
        got["plain"] = fugue_sql("SELECT k FROM t WHERE v > 10", t=df,
                                 engine=engine, as_fugue=True).as_pandas()

    ev = _record(tmp_path, queries)
    assert got["sorted"]["k"].tolist() == [999, 998, 997, 996, 995]
    assert len(got["plain"]) == 989
    (sort,) = ev["engine.sort"]
    assert sort[2] == {"devices": len(devices.split(","))}
    inside = [e for e in ev["engine.dispatch"] if sort[0] <= e[0] and e[1] <= sort[1]]
    assert inside and all(e[2]["devices"] == sort[2]["devices"] for e in inside)
    engine.stop()
