"""The generated ORDERS against each rule of TPC-H's clause 4.2.3, and
against the LINEITEM the same seed gives."""

import hashlib
import re

import numpy as np
import pyarrow.parquet as pq
import pytest

import datagen

N_ORDERS, FILES, SEED = 3000, 4, 2**33 + 17


def _sha(directory) -> tuple:
    return tuple(hashlib.sha256((directory / f"part{i}.parquet").read_bytes()).hexdigest()
                 for i in range(FILES))


@pytest.fixture(scope="module")
def made(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("orders")
    orders_dir, cols = datagen.gen_table(str(tmp / "o"), "orders", N_ORDERS, FILES, SEED, datagen.ORDERS_COLS)
    items_dir, _ = datagen.gen_lineitem(str(tmp / "l"), N_ORDERS, FILES, SEED, cols=())
    o, li = pq.read_table(orders_dir), pq.read_table(items_dir)
    return tmp, cols, {n: o.column(n).to_pylist() for n in o.column_names}, \
        {n: li.column(n).to_pylist() for n in ("l_orderkey", "l_linenumber", "l_extendedprice",
                                                 "l_tax", "l_discount", "l_linestatus", "l_shipdate")}


def _lines_by_order(li: dict) -> dict:
    by = {}
    for i, k in enumerate(li["l_orderkey"]):
        by.setdefault(k, []).append(i)
    return by


def test_the_files_and_the_columns_returned(made):
    tmp, cols, o, _li = made
    assert sorted(p.name for p in (tmp / "o").iterdir()) == ["orders"]     # no LINEITEM written
    t = pq.read_table(str(tmp / "o" / "orders"))
    assert tuple(t.column_names) == datagen.ORDERS_COLS and t.num_rows == N_ORDERS
    assert [str(t.schema.field(n).type) for n in datagen.ORDERS_COLS] == [
        "int64", "int64", "string", "double", "date32[day]", "string", "string", "int32", "string"]
    per_file = [pq.read_metadata(str(tmp / "o" / "orders" / f"part{i}.parquet")).num_rows for i in range(FILES)]
    assert per_file == [N_ORDERS // FILES] * FILES
    assert list(cols) == list(datagen.ORDERS_COLS)
    for name in datagen.ORDERS_COLS:
        col = t.column(name)
        want = col.cast("int32").to_pylist() if name == "o_orderdate" else col.to_pylist()
        assert cols[name].tolist() == want, name
    assert cols["o_comment"].dtype == object and cols["o_orderkey"].dtype == np.int64
    with pytest.raises(ValueError, match="o_no_such_column"):
        datagen.gen_table(str(tmp / "x"), "orders", 10, 1, 1, cols=["o_comment", "o_no_such_column"])
    with pytest.raises(ValueError, match="customer"):
        datagen.gen_table(str(tmp / "y"), "customer", 10, 1, 1)


def test_keys_are_lineitems_orders_in_key_order(made):
    _tmp, _cols, o, li = made
    assert o["o_orderkey"] == sorted(set(li["l_orderkey"]))
    assert o["o_orderkey"] == datagen.order_key(np.arange(N_ORDERS)).tolist()


def test_total_price_status_and_date_follow_from_the_lines(made):
    _tmp, cols, o, li = made
    by = _lines_by_order(li)
    start = datagen.STARTDATE
    for i, key in enumerate(o["o_orderkey"]):
        lines = sorted(by[key], key=lambda r: li["l_linenumber"][r])
        total = 0.0
        for r in lines:
            total += li["l_extendedprice"][r] * (1.0 + li["l_tax"][r]) * (1.0 - li["l_discount"][r])
        assert o["o_totalprice"][i] == total, key
        status = {li["l_linestatus"][r] for r in lines}
        assert o["o_orderstatus"][i] == (status.pop() if len(status) == 1 else "P"), key
        ship = [(li["l_shipdate"][r] - o["o_orderdate"][i]).days for r in lines]
        assert min(ship) >= 1 and max(ship) <= 121, key
    days = cols["o_orderdate"]
    assert days.min() >= start and days.max() <= datagen.ENDDATE - 151
    assert set(o["o_orderstatus"]) == {"F", "O", "P"}


def test_customers_priorities_clerks_and_comments(made):
    _tmp, cols, o, _li = made
    cust = cols["o_custkey"]
    assert cust.min() >= 1 and cust.max() <= datagen.customers(N_ORDERS) == N_ORDERS // 10
    assert not np.any(cust % 3 == 0)
    # uniform over the keys that are no multiple of 3: every one is drawn at this size
    assert len(np.unique(cust)) == datagen.customers(N_ORDERS) - datagen.customers(N_ORDERS) // 3
    assert set(o["o_orderpriority"]) == set(datagen.PRIORITIES)
    clerks = {int(re.fullmatch(r"Clerk#(\d{9})", c).group(1)) for c in o["o_clerk"]}
    assert min(clerks) >= 1 and max(clerks) <= datagen.clerks(N_ORDERS) == max(N_ORDERS // 1500, 1)
    assert set(o["o_shippriority"]) == {0}
    lengths = np.array([len(c) for c in o["o_comment"]])
    assert lengths.min() == 19 and lengths.max() == 78
    assert len(set(o["o_comment"])) > 0.99 * N_ORDERS


def test_same_seed_same_bytes_another_seed_others(made, tmp_path):
    tmp, _cols, _o, _li = made
    datagen.gen_table(str(tmp_path / "a"), "orders", N_ORDERS, FILES, SEED)
    datagen.gen_table(str(tmp_path / "b"), "orders", N_ORDERS, FILES, SEED + 1)
    first = _sha(tmp / "o" / "orders")
    assert _sha(tmp_path / "a" / "orders") == first
    assert set(_sha(tmp_path / "b" / "orders")).isdisjoint(first)
