"""Aggregate / Sort / Limit engine tests — differential vs pyarrow compute.

The reference delegates these to Spark; for us they are engine nodes
(VERDICT round-1 item 6). Differential style mirrors the reference's
``QueryTest.checkAnswer`` pattern: same answer as an independent engine.
"""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu import functions as F
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.indexes.covering import CoveringIndexConfig


@pytest.fixture
def agg_data(tmp_path):
    rng = np.random.default_rng(5)
    n = 500
    t = pa.table(
        {
            "g": pa.array([f"k{int(x)}" for x in rng.integers(0, 7, n)]),
            "h": pa.array(rng.integers(0, 3, n), type=pa.int64()),
            "x": pa.array(rng.integers(-50, 50, n), type=pa.int64()),
            "y": pa.array(rng.normal(0, 10, n)),
            "s": pa.array(
                [["apple", "pear", "fig", None][int(x)] for x in rng.integers(0, 4, n)]
            ),
            "z": pa.array(
                [None if i % 11 == 0 else float(i % 13) for i in range(n)]
            ),
        }
    )
    d = tmp_path / "agg"
    d.mkdir()
    for i in range(2):
        pq.write_table(t.slice(i * 250, 250), d / f"p{i}.parquet")
    return str(d), t


def arrow_groupby(t, keys, aggs):
    """pyarrow reference implementation -> sorted table."""
    gb = t.group_by(keys)
    out = gb.aggregate(aggs)
    return out.sort_by([(k, "ascending") for k in keys])


def sorted_by(t, keys):
    return t.sort_by([(k, "ascending") for k in keys])


class TestAggregates:
    def test_grouped_sum_count_min_max_avg(self, session, agg_data):
        d, t = agg_data
        df = session.read.parquet(d)
        got = (
            df.group_by("g")
            .agg(
                F.sum("x").alias("sx"),
                F.count().alias("n"),
                F.count("z").alias("nz"),
                F.min("x").alias("mnx"),
                F.max("y").alias("mxy"),
                F.avg("x").alias("ax"),
            )
            .collect()
            .sort_by([("g", "ascending")])
        )
        ref = arrow_groupby(
            t,
            ["g"],
            [
                ("x", "sum"),
                ("g", "count"),
                ("z", "count"),
                ("x", "min"),
                ("y", "max"),
                ("x", "mean"),
            ],
        )
        assert got.column("sx").to_pylist() == ref.column("x_sum").to_pylist()
        assert got.column("n").to_pylist() == ref.column("g_count").to_pylist()
        assert got.column("nz").to_pylist() == ref.column("z_count").to_pylist()
        assert got.column("mnx").to_pylist() == ref.column("x_min").to_pylist()
        assert got.column("mxy").to_pylist() == pytest.approx(
            ref.column("y_max").to_pylist()
        )
        assert got.column("ax").to_pylist() == pytest.approx(
            ref.column("x_mean").to_pylist()
        )

    def test_multi_key_group(self, session, agg_data):
        d, t = agg_data
        df = session.read.parquet(d)
        got = (
            df.group_by("g", "h")
            .agg(F.sum("x").alias("sx"))
            .collect()
            .sort_by([("g", "ascending"), ("h", "ascending")])
        )
        ref = arrow_groupby(t, ["g", "h"], [("x", "sum")]).sort_by(
            [("g", "ascending"), ("h", "ascending")]
        )
        assert got.column("g").to_pylist() == ref.column("g").to_pylist()
        assert got.column("h").to_pylist() == ref.column("h").to_pylist()
        assert got.column("sx").to_pylist() == ref.column("x_sum").to_pylist()

    def test_global_aggregate(self, session, agg_data):
        d, t = agg_data
        df = session.read.parquet(d)
        got = df.agg(
            F.count().alias("n"), F.sum("x").alias("sx"), F.avg("y").alias("ay")
        ).collect()
        assert got.num_rows == 1
        assert got.column("n")[0].as_py() == t.num_rows
        assert got.column("sx")[0].as_py() == pc.sum(t.column("x")).as_py()
        assert got.column("ay")[0].as_py() == pytest.approx(
            pc.mean(t.column("y")).as_py()
        )

    def test_null_group_and_null_aggs(self, session, agg_data):
        d, t = agg_data
        df = session.read.parquet(d)
        # group by a column containing nulls: nulls form one group (SQL)
        got = (
            df.group_by("s")
            .agg(F.count().alias("n"), F.sum("x").alias("sx"))
            .collect()
        )
        got_by_key = {
            r["s"]: (r["n"], r["sx"]) for r in got.to_pylist()
        }
        ref = t.group_by("s").aggregate([([], "count_all"), ("x", "sum")])
        ref_by_key = {
            r["s"]: (r["count_all"], r["x_sum"]) for r in ref.to_pylist()
        }
        assert got_by_key == ref_by_key
        assert None in got_by_key  # the null group exists

    def test_string_min_max(self, session, agg_data):
        d, t = agg_data
        df = session.read.parquet(d)
        got = (
            df.group_by("h")
            .agg(F.min("s").alias("mn"), F.max("s").alias("mx"))
            .collect()
            .sort_by([("h", "ascending")])
        )
        ref = arrow_groupby(t, ["h"], [("s", "min"), ("s", "max")])
        assert got.column("mn").to_pylist() == ref.column("s_min").to_pylist()
        assert got.column("mx").to_pylist() == ref.column("s_max").to_pylist()

    def test_all_null_group_sum_is_null(self, session, tmp_path):
        t = pa.table(
            {
                "g": ["a", "a", "b"],
                "v": pa.array([None, None, 1.5], type=pa.float64()),
            }
        )
        d = tmp_path / "n"
        d.mkdir()
        pq.write_table(t, d / "p.parquet")
        df = session.read.parquet(str(d))
        got = (
            df.group_by("g")
            .agg(F.sum("v").alias("sv"), F.min("v").alias("mv"))
            .collect()
            .sort_by([("g", "ascending")])
        )
        assert got.column("sv").to_pylist() == [None, 1.5]
        assert got.column("mv").to_pylist() == [None, 1.5]

    def test_empty_input_global_agg(self, session, tmp_path):
        t = pa.table({"v": pa.array([], type=pa.int64())})
        d = tmp_path / "e"
        d.mkdir()
        pq.write_table(t, d / "p.parquet")
        df = session.read.parquet(str(d))
        got = df.agg(F.count().alias("n"), F.sum("v").alias("sv")).collect()
        assert got.column("n").to_pylist() == [0]
        assert got.column("sv").to_pylist() == [None]

    def test_agg_over_filter(self, session, agg_data):
        d, t = agg_data
        df = session.read.parquet(d)
        got = (
            df.filter(df["x"] > 0)
            .group_by("g")
            .agg(F.sum("x").alias("sx"))
            .collect()
            .sort_by([("g", "ascending")])
        )
        ft = t.filter(pc.greater(t.column("x"), 0))
        ref = arrow_groupby(ft, ["g"], [("x", "sum")])
        assert got.column("sx").to_pylist() == ref.column("x_sum").to_pylist()


class TestSortLimit:
    def test_sort_single_key(self, session, agg_data):
        d, t = agg_data
        df = session.read.parquet(d)
        got = df.sort("x").collect()
        ref = t.sort_by([("x", "ascending")])
        assert got.column("x").to_pylist() == ref.column("x").to_pylist()

    def test_sort_descending_and_multi_key(self, session, agg_data):
        d, t = agg_data
        df = session.read.parquet(d)
        got = df.sort("g", ("x", False)).collect()
        ref = t.sort_by([("g", "ascending"), ("x", "descending")])
        assert got.column("g").to_pylist() == ref.column("g").to_pylist()
        assert got.column("x").to_pylist() == ref.column("x").to_pylist()

    def test_sort_string_and_float_with_nulls(self, session, agg_data):
        d, t = agg_data
        df = session.read.parquet(d)
        got = df.sort("s", "z").collect()
        ref = t.sort_by([("s", "ascending"), ("z", "ascending")])
        assert got.column("s").to_pylist() == ref.column("s").to_pylist()
        assert got.column("z").to_pylist() == ref.column("z").to_pylist()

    def test_sort_floats_negative(self, session, tmp_path):
        vals = [3.5, -1.25, 0.0, -0.0, float("inf"), -float("inf"), 2.0, -7.5]
        t = pa.table({"v": pa.array(vals, type=pa.float64())})
        d = tmp_path / "f"
        d.mkdir()
        pq.write_table(t, d / "p.parquet")
        df = session.read.parquet(str(d))
        got = df.sort("v").collect().column("v").to_pylist()
        assert got == sorted(vals)
        got_desc = df.sort(("v", False)).collect().column("v").to_pylist()
        assert got_desc == sorted(vals, reverse=True)

    def test_limit(self, session, agg_data):
        d, t = agg_data
        df = session.read.parquet(d)
        got = df.sort("x").limit(7).collect()
        assert got.num_rows == 7
        ref = t.sort_by([("x", "ascending")]).slice(0, 7)
        assert got.column("x").to_pylist() == ref.column("x").to_pylist()
        assert df.limit(10**9).collect().num_rows == t.num_rows

    def test_index_served_filter_then_aggregate(self, session, agg_data):
        """Bench config 2 shape: range filter + aggregate over an index."""
        d, t = agg_data
        hs = Hyperspace(session)
        df = session.read.parquet(d)
        hs.create_index(df, CoveringIndexConfig("x_idx", ["x"], ["g", "y"]))
        q = lambda f: (
            f.filter(f["x"] > 10)
            .group_by("g")
            .agg(F.count().alias("n"), F.avg("y").alias("ay"))
        )
        session.disable_hyperspace()
        base = q(df).collect().sort_by([("g", "ascending")])
        session.enable_hyperspace()
        plan = q(df).explain()
        assert "Hyperspace(Type: CI, Name: x_idx" in plan, plan
        got = q(df).collect().sort_by([("g", "ascending")])
        assert got.column("g").to_pylist() == base.column("g").to_pylist()
        assert got.column("n").to_pylist() == base.column("n").to_pylist()
        assert got.column("ay").to_pylist() == pytest.approx(
            base.column("ay").to_pylist()
        )

    def test_nan_min_max_spark_semantics(self, session, tmp_path):
        """NaN > +inf (Spark float ordering, consistent with sort)."""
        t = pa.table(
            {
                "g": ["a", "a", "b", "b", "c"],
                "v": pa.array(
                    [1.0, float("nan"), float("nan"), float("nan"), 2.0],
                    type=pa.float64(),
                ),
            }
        )
        d = tmp_path / "nan"
        d.mkdir()
        pq.write_table(t, d / "p.parquet")
        df = session.read.parquet(str(d))
        got = (
            df.group_by("g")
            .agg(F.min("v").alias("mn"), F.max("v").alias("mx"))
            .collect()
            .sort_by([("g", "ascending")])
        )
        mn = got.column("mn").to_pylist()
        mx = got.column("mx").to_pylist()
        assert mn[0] == 1.0 and np.isnan(mx[0])  # NaN wins max
        assert np.isnan(mn[1]) and np.isnan(mx[1])  # all-NaN group
        assert mn[2] == 2.0 and mx[2] == 2.0

    def test_plan_time_type_validation(self, session, agg_data):
        from hyperspace_tpu.exceptions import HyperspaceException

        d, t = agg_data
        df = session.read.parquet(d)
        with pytest.raises(HyperspaceException, match="avg"):
            df.group_by("g").agg(F.avg("s")).schema()
        with pytest.raises(HyperspaceException, match="sum"):
            df.group_by("g").agg(F.sum("s")).schema()


def test_segment_ops_host_device_equivalent():
    """The small-input host reductions and the device segment kernels must
    agree (incl. int64 exactness, null handling and NaN min/max rules)."""
    import numpy as np

    from hyperspace_tpu.ops import aggregate as A

    rng = np.random.default_rng(1)
    n, g = 5000, 37
    gid = rng.integers(0, g, n)
    ints = rng.integers(-(2**40), 2**40, n, dtype=np.int64)
    flts = rng.normal(size=n)
    flts[rng.random(n) < 0.05] = np.nan
    valid = rng.random(n) > 0.1

    def both(fn, *args):
        host = fn(*args)
        old = A._HOST_AGG_MAX_ROWS
        try:
            A._HOST_AGG_MAX_ROWS = 0
            dev = fn(*args)
        finally:
            A._HOST_AGG_MAX_ROWS = old
        return host, dev

    (hs, hc), (ds, dc) = both(A.segment_sum_count, gid, ints, valid, g)
    assert np.array_equal(hs, ds) and np.array_equal(hc, dc)
    for mode in ("min", "max"):
        h, d = both(A.segment_minmax, gid, ints, valid, g, mode)
        assert np.array_equal(h, d), mode
    h, d = both(A.segment_count, gid, valid, n, g)
    assert np.array_equal(h, d)

    # floats never reach the device kernels, whatever the row count: the
    # TPU holds no IEEE double, so its float answers are not the host's
    kernels = (A._seg_sum_count, A._seg_min, A._seg_max)
    before = [k._cache_size() for k in kernels]
    (hs, hc), (ds, dc) = both(A.segment_sum_count, gid, flts, valid, g)
    assert np.array_equal(hs, ds, equal_nan=True) and np.array_equal(hc, dc)
    for mode in ("min", "max"):
        h, d = both(A.segment_minmax, gid, flts, valid, g, mode)
        assert np.array_equal(h, d, equal_nan=True), mode
    assert [k._cache_size() for k in kernels] == before


def test_uint8_sum_does_not_wrap():
    import numpy as np

    from hyperspace_tpu.ops import aggregate as A

    gid = np.zeros(2, dtype=np.int64)
    vals = np.array([200, 200], dtype=np.uint8)
    s, c = A.segment_sum_count(gid, vals, None, 1)
    assert int(s[0]) == 400 and int(c[0]) == 2
