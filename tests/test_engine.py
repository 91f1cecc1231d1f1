"""Query engine tests: expressions, executor, joins.

Differential style (the reference's `checkAnswer` pattern,
``E2EHyperspaceRulesTest.scala:76-120``): engine results are compared
against independint pyarrow/python evaluation of the same query.
"""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu.io.columnar import ColumnarBatch
from hyperspace_tpu.plan import expressions as E


@pytest.fixture
def batch():
    return ColumnarBatch.from_arrow(
        pa.table(
            {
                "k": pa.array([1, 2, None, 4, 5], type=pa.int64()),
                "v": pa.array([10.0, 20.0, 30.0, None, 50.0]),
                "s": pa.array(["b", "a", "c", None, "b"]),
            }
        )
    )


def rows(mask):
    return np.nonzero(mask)[0].tolist()


class TestExpressions:
    def test_numeric_comparisons(self, batch):
        c = E.Col("k")
        assert rows(E.filter_mask(c > 1, batch)) == [1, 3, 4]
        assert rows(E.filter_mask(c == 4, batch)) == [3]
        assert rows(E.filter_mask(c <= 2, batch)) == [0, 1]
        assert rows(E.filter_mask(c != 2, batch)) == [0, 3, 4]

    def test_null_semantics(self, batch):
        k, v = E.Col("k"), E.Col("v")
        # NULL rows never pass comparisons, even negated ones
        assert rows(E.filter_mask(~(k > 1), batch)) == [0]
        assert rows(E.filter_mask(E.IsNull(k), batch)) == [2]
        assert rows(E.filter_mask(k.is_not_null(), batch)) == [0, 1, 3, 4]
        # Kleene OR: (k>1) OR (v>0) — row 2 has k null but v=30>0 ⇒ true
        assert rows(E.filter_mask((k > 1) | (v > 0.0), batch)) == [0, 1, 2, 3, 4]
        # Kleene AND: row 3 v null ⇒ unknown
        assert rows(E.filter_mask((k > 1) & (v > 0.0), batch)) == [1, 4]

    def test_string_comparisons(self, batch):
        s = E.Col("s")
        assert rows(E.filter_mask(s == "b", batch)) == [0, 4]
        assert rows(E.filter_mask(s != "b", batch)) == [1, 2]
        assert rows(E.filter_mask(s < "b", batch)) == [1]
        assert rows(E.filter_mask(s >= "b", batch)) == [0, 2, 4]
        # literal absent from dictionary
        assert rows(E.filter_mask(s == "zz", batch)) == []
        assert rows(E.filter_mask(s <= "aa", batch)) == [1]

    def test_in(self, batch):
        assert rows(E.filter_mask(E.Col("k").isin(1, 5, 99), batch)) == [0, 4]
        assert rows(E.filter_mask(E.Col("s").isin("a", "c", "zz"), batch)) == [1, 2]

    def test_references_and_conjuncts(self):
        e = (E.Col("a") > 1) & (E.Col("b") == E.Col("c"))
        assert E.references(e) == {"a", "b", "c"}
        assert len(E.split_conjuncts(e)) == 2
        assert E.equi_join_pairs(E.Col("x") == E.Col("y")) == [("x", "y")]
        assert E.equi_join_pairs(E.Col("x") > E.Col("y")) is None

    def test_expr_bool_raises(self):
        with pytest.raises(TypeError):
            bool(E.Col("a") == E.Col("b"))


class TestDeviceFilter:
    """Device kernel must agree with the host evaluator on every case."""

    EXPRS = [
        lambda: E.Col("k") > 1,
        lambda: E.Col("k") == 4,
        lambda: ~(E.Col("k") > 1),
        lambda: (E.Col("k") > 1) | (E.Col("v") > 0.0),
        lambda: (E.Col("k") > 1) & (E.Col("v") > 0.0),
        lambda: E.Col("s") == "b",
        lambda: E.Col("s") < "b",
        lambda: E.Col("s") >= "b",
        lambda: E.Col("s") == "zz",
        lambda: E.Col("k").isin(1, 5, 99),
        lambda: E.Col("s").isin("a", "c", "zz"),
        lambda: E.IsNull(E.Col("k")),
        lambda: E.Col("k").is_not_null() & (E.Col("s") != "b"),
        lambda: E.Col("k") == E.Col("k"),
    ]

    @pytest.mark.parametrize("mk", EXPRS)
    def test_device_matches_host(self, batch, mk):
        from hyperspace_tpu.ops.filter import Unsupported, device_filter_mask

        e = mk()
        if "v" in E.references(e):
            # float64 columns are the host's: the TPU holds no IEEE
            # double, so its compare is not the host's compare
            with pytest.raises(Unsupported, match="float64"):
                device_filter_mask(e, batch)
            return
        np.testing.assert_array_equal(
            device_filter_mask(e, batch), E.filter_mask(e, batch)
        )


@pytest.fixture
def two_tables(tmp_path, session):
    rng = np.random.default_rng(7)
    n1, n2 = 500, 300
    orders = pa.table(
        {
            "o_key": pa.array(rng.integers(0, 100, n1), type=pa.int64()),
            "o_val": pa.array(rng.normal(size=n1)),
            "o_tag": pa.array([f"t{int(x)%5}" for x in rng.integers(0, 100, n1)]),
        }
    )
    items = pa.table(
        {
            "l_key": pa.array(rng.integers(0, 100, n2), type=pa.int64()),
            "l_qty": pa.array(rng.integers(1, 50, n2), type=pa.int64()),
        }
    )
    d1, d2 = tmp_path / "orders", tmp_path / "items"
    d1.mkdir(), d2.mkdir()
    pq.write_table(orders, d1 / "part-0.parquet")
    pq.write_table(items, d2 / "part-0.parquet")
    return str(d1), str(d2), orders, items


class TestExecutor:
    def test_scan_collect(self, session, sample_parquet):
        df = session.read.parquet(sample_parquet)
        out = df.collect()
        assert out.num_rows == 300
        assert set(df.columns) == {"date", "rguid", "clicks", "query", "imprs"}

    def test_filter_project_differential(self, session, sample_parquet):
        import pyarrow.compute as pc

        df = session.read.parquet(sample_parquet)
        got = (
            df.filter((df["clicks"] > 500) & (df["query"] == "banana"))
            .select("clicks", "imprs")
            .collect()
        )
        raw = df.collect()
        want = raw.filter(
            pc.and_(
                pc.greater(raw.column("clicks"), 500),
                pc.equal(raw.column("query"), "banana"),
            )
        ).select(["clicks", "imprs"])
        assert got.sort_by("clicks").equals(want.sort_by("clicks"))
        assert got.num_rows > 0

    def test_join_differential(self, session, two_tables):
        d1, d2, orders, items = two_tables
        dfo = session.read.parquet(d1)
        dfi = session.read.parquet(d2)
        got = (
            dfo.join(dfi, on=dfo["o_key"] == dfi["l_key"])
            .select("o_key", "l_qty")
            .collect()
        )
        # independent check via python dict join
        import collections

        right = collections.defaultdict(list)
        for k, q in zip(
            items.column("l_key").to_pylist(), items.column("l_qty").to_pylist()
        ):
            right[k].append(q)
        want = []
        for k in orders.column("o_key").to_pylist():
            for q in right.get(k, []):
                want.append((k, q))
        got_pairs = sorted(
            zip(got.column("o_key").to_pylist(), got.column("l_qty").to_pylist())
        )
        assert got_pairs == sorted(want)
        assert len(got_pairs) > 0

    def test_string_filter_differential(self, session, two_tables):
        import pyarrow.compute as pc

        d1, _d2, orders, _items = two_tables
        dfo = session.read.parquet(d1)
        got = dfo.filter(dfo["o_tag"] == "t3").count()
        want = orders.filter(pc.equal(orders.column("o_tag"), "t3")).num_rows
        assert got == want

    def test_string_key_join(self, session, tmp_path):
        a = pa.table({"tag_a": ["x", "y", "z", "x"], "va": [1, 2, 3, 4]})
        b = pa.table({"tag_b": ["x", "x", "q"], "vb": [10, 20, 30]})
        (tmp_path / "a").mkdir(), (tmp_path / "b").mkdir()
        pq.write_table(a, tmp_path / "a" / "p.parquet")
        pq.write_table(b, tmp_path / "b" / "p.parquet")
        dfa = session.read.parquet(str(tmp_path / "a"))
        dfb = session.read.parquet(str(tmp_path / "b"))
        got = dfa.join(dfb, on=dfa["tag_a"] == dfb["tag_b"]).collect()
        pairs = sorted(
            zip(got.column("va").to_pylist(), got.column("vb").to_pylist())
        )
        assert pairs == [(1, 10), (1, 20), (4, 10), (4, 20)]

    def test_csv_scan(self, session, tmp_path):
        p = tmp_path / "c"
        p.mkdir()
        (p / "a.csv").write_text("x,y\n1,a\n2,b\n3,a\n")
        df = session.read.csv(str(p))
        assert df.filter(df["y"] == "a").count() == 2

    def test_empty_result(self, session, sample_parquet):
        df = session.read.parquet(sample_parquet)
        out = df.filter(df["clicks"] > 10**9).select("clicks").collect()
        assert out.num_rows == 0
