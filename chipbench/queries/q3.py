"""TPC-H Q3, "Shipping Priority", through the program's logical plan.

    SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue,
           o_orderdate, o_shippriority
    FROM customer, orders, lineitem
    WHERE c_mktsegment = SEGMENT AND c_custkey = o_custkey
      AND l_orderkey = o_orderkey AND o_orderdate < DATE AND l_shipdate > DATE
    GROUP BY l_orderkey, o_orderdate, o_shippriority
    ORDER BY revenue DESC, o_orderdate
    LIMIT n

``tables`` draws the three tables from the seed after the specification's
clause 4.2.3, with only the columns Q3 reads, in the configuration's
schema order.  ``run`` builds the ``LogicalPlan`` (three filtered scans,
customer joined with orders on the customer key, that joined with lineitem
on the order key, the group-by and the ORDER BY ... LIMIT), lowers it with
``compile_plan`` and runs it with ``replan="measured"`` and ``stages=True``:
each task materializes its output before the next starts, so each is
planned with the whole budget.

The reference below is this module's own, in plain numpy int64: each task's
output from its inputs, with the columns, predicates and constants taken
from the configuration and from the row layouts this module names (``CO``,
``COL``, ``GROUPED``), never from the plan.  The joins are told apart by
their build side's width, which the schema fixes: customer's rows or the
first join's.  Joins and the aggregation are compared as multisets, the
ORDER BY ... LIMIT row for row.
"""

from __future__ import annotations

import datetime
from typing import Dict, List

import numpy as np

from chipbench import check

TABLES = ("customer", "orders", "lineitem")
# Row layouts of the tasks' outputs: the first join (customer, orders), the
# second (with lineitem), and the aggregation.
CO = ("c_custkey", "o_orderkey", "o_orderdate", "o_shippriority")
COL = ("o_orderkey", "o_orderdate", "o_shippriority", "l_extendedprice", "l_discount")
GROUPS = ("o_orderkey", "o_orderdate", "o_shippriority")
GROUPED = GROUPS + ("revenue", "count")


def columns(config: dict, table: str) -> List[str]:
    return [c["name"] for c in config["schema"][table]["columns"]]


def col(config: dict, table: str, name: str) -> int:
    return columns(config, table).index(name)


def day(config: dict, iso: str) -> int:
    """A date as its day number from the configuration's epoch."""
    epoch = datetime.date.fromisoformat(config["dates"]["epoch"])
    return (datetime.date.fromisoformat(iso) - epoch).days


def parameters(config: dict) -> dict:
    """Q3's substitution parameters as the data encodes them."""
    p = config["parameters"]
    return {"segment": config["segments"].index(p["segment"]),
            "date": day(config, p["date"]), "limit": int(p["limit"])}


# --------------------------------------------------------------------------
# Data
# --------------------------------------------------------------------------


def _rng(seed: int, index: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(index)])


def _table(config: dict, name: str, cols: Dict[str, np.ndarray]) -> np.ndarray:
    return np.stack([cols[c] for c in columns(config, name)], axis=1).astype(np.int64)


def tables(config: dict, seed: int) -> Dict[str, np.ndarray]:
    """customer, orders and lineitem at the configuration's scale factor."""
    sf = config["scale_factor"]
    schema = config["schema"]
    n_c = int(round(sf * schema["customer"]["rows_per_sf"]))
    n_o = int(round(sf * schema["orders"]["rows_per_sf"]))
    n_p = int(round(sf * schema["lineitem"]["parts_per_sf"]))
    start = day(config, config["dates"]["start"])
    last_order = day(config, config["dates"]["end"]) - 151

    r = _rng(seed, 0)
    customer = _table(config, "customer", {
        "c_custkey": np.arange(1, n_c + 1),
        "c_mktsegment": r.integers(0, len(config["segments"]), n_c)})

    r = _rng(seed, 1)
    i = np.arange(n_o)
    # The i-th customer key that is not a multiple of 3.
    j = r.integers(0, n_c - n_c // 3, n_o)
    orderdate = r.integers(start, last_order + 1, n_o)
    orders = _table(config, "orders", {
        "o_orderkey": (i // 8) * 32 + i % 8 + 1,
        "o_custkey": (j // 2) * 3 + j % 2 + 1,
        "o_orderdate": orderdate,
        "o_shippriority": np.zeros(n_o, np.int64)})

    r = _rng(seed, 2)
    lo, hi = schema["lineitem"]["lines_per_order"]
    owner = np.repeat(i, r.integers(lo, hi + 1, n_o))
    n_l = len(owner)
    partkey = r.integers(1, n_p + 1, n_l)
    retailprice = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)  # cents
    lineitem = _table(config, "lineitem", {
        "l_orderkey": orders[owner, col(config, "orders", "o_orderkey")],
        "l_extendedprice": r.integers(1, 51, n_l) * retailprice,
        "l_discount": r.integers(0, 11, n_l),
        "l_shipdate": orderdate[owner] + r.integers(1, 122, n_l)})
    return {"customer": customer, "orders": orders, "lineitem": lineitem}


def place(backend, tables: Dict[str, np.ndarray], config: dict) -> Dict[str, object]:
    """Seed each table's pages on the backend (bottom tier, no transfer
    rounds), as many rows a page as fill ``page_bytes``."""
    from repro.remote.rows import page_rows
    from repro.remote.simulator import Relation

    out = {}
    for name, t in tables.items():
        rows = page_rows(t.shape[1], None, config["page_bytes"])
        pages = [t[i:i + rows].copy() for i in range(0, len(t), rows)]
        out[name] = Relation(page_ids=backend.put_local(pages), rows_per_page=rows,
                             total_rows=len(t))
    return out


# --------------------------------------------------------------------------
# The query
# --------------------------------------------------------------------------


def run(session, inputs: Dict[str, object], config: dict, params: dict, span):
    from repro.engine.plan import LogicalPlan, compile_plan
    from repro.remote.rows import page_rows

    q = parameters(config)
    c = lambda t, n: col(config, t, n)  # noqa: E731
    # The planner's estimates: each predicate's share of its table under the
    # specification's uniform distributions, the joins' outputs as if the
    # predicates were independent (4 lines an order).
    start = day(config, config["dates"]["start"])
    last_order = day(config, config["dates"]["end"]) - 151
    date_share = (q["date"] - start) / (last_order - start + 1)
    segment_sel = 1.0 / len(config["segments"])
    ship_sel = 1.0 - date_share
    co_rows = inputs["orders"].total_rows * segment_sel * date_share
    co_pages = co_rows / page_rows(len(CO), None, config["page_bytes"])
    col_pages = 4 * co_rows * ship_sel / page_rows(len(COL), None, config["page_bytes"])
    op = dict(sigma=params["sigma"], partitions=params["partitions"],
              page_bytes=config["page_bytes"])
    with span("compile_plan"):
        lp = LogicalPlan("q3")
        scan = {t: lp.scan(t, inputs[t], rows_per_page=inputs[t].rows_per_page)
                for t in TABLES}
        customer = lp.filter(scan["customer"], segment_sel, name="c_mktsegment",
                             where=[(c("customer", "c_mktsegment"), "==", q["segment"])])
        orders = lp.filter(scan["orders"], date_share, name="o_orderdate",
                           where=[(c("orders", "o_orderdate"), "<", q["date"])])
        lineitem = lp.filter(scan["lineitem"], ship_sel, name="l_shipdate",
                             where=[(c("lineitem", "l_shipdate"), ">", q["date"])])
        co = lp.join(customer, orders, out_pages=co_pages,
                     on=(c("customer", "c_custkey"), c("orders", "o_custkey")),
                     keep=((), [c("orders", n) for n in CO[1:]]), **op)
        col_ = lp.join(co, lineitem, out_pages=col_pages,
                       on=(CO.index("o_orderkey"), c("lineitem", "l_orderkey")),
                       keep=([CO.index(n) for n in COL[1:3]],
                             [c("lineitem", n) for n in COL[3:]]), **op)
        revenue = ("*", COL.index("l_extendedprice"),
                   ("-", ("lit", 100), COL.index("l_discount")))
        grouped = lp.aggregate(col_, out_pages=col_pages, by=[COL.index(n) for n in GROUPS],
                               sums=[revenue], **op)
        lp.sort(grouped, by=[(GROUPED.index("revenue"), True),
                             (GROUPED.index("o_orderdate"), False)],
                limit=q["limit"], page_bytes=config["page_bytes"])
        plan = compile_plan(session, lp)
    with span("session.run"):
        return plan.run(session, replan="measured", stages=True)


# --------------------------------------------------------------------------
# The reference
# --------------------------------------------------------------------------


def equijoin(build: np.ndarray, bkey: int, probe: np.ndarray, pkey: int):
    """Index pairs ``(build row, probe row)`` of every match of
    ``build[:, bkey] == probe[:, pkey]``."""
    order = np.argsort(build[:, bkey], kind="stable")
    keys = build[order, bkey]
    lo = np.searchsorted(keys, probe[:, pkey], side="left")
    n = np.searchsorted(keys, probe[:, pkey], side="right") - lo
    p_idx = np.repeat(np.arange(len(probe)), n)
    b_idx = order[np.repeat(lo - (np.cumsum(n) - n), n) + np.arange(int(n.sum()))]
    return b_idx, p_idx


def join(ins: Dict[str, np.ndarray], config: dict) -> np.ndarray:
    """The first join's rows (``CO``) from customer and orders, or the
    second's (``COL``) from the first's and lineitem, each side filtered by
    Q3's predicates on it.  Inputs of neither layout (a control's, say)
    join to nothing."""
    q = parameters(config)
    c = lambda t, n: col(config, t, n)  # noqa: E731
    build, probe = ins["build"], ins["probe"]
    width = build.shape[1] if build.ndim == 2 else 0
    if width == len(columns(config, "customer")):
        cust = build[build[:, c("customer", "c_mktsegment")] == q["segment"]]
        orders = probe[probe[:, c("orders", "o_orderdate")] < q["date"]]
        b, p = equijoin(cust, c("customer", "c_custkey"), orders, c("orders", "o_custkey"))
        return np.stack([cust[b, c("customer", "c_custkey")]]
                        + [orders[p, c("orders", n)] for n in CO[1:]], axis=1)
    if width != len(CO):
        return np.empty((0, len(COL)), np.int64)
    lineitem = probe[probe[:, c("lineitem", "l_shipdate")] > q["date"]]
    b, p = equijoin(build, CO.index("o_orderkey"), lineitem, c("lineitem", "l_orderkey"))
    return np.stack([build[b, CO.index(n)] for n in COL[:3]]
                    + [lineitem[p, c("lineitem", n)] for n in COL[3:]], axis=1)


def aggregate(ins: Dict[str, np.ndarray], config: dict) -> np.ndarray:
    """``GROUPED`` rows: revenue = sum(l_extendedprice * (100 - l_discount))
    per group, summed in int64, and the group's row count.  Rows that are
    not the second join's (a control's, say) group to nothing."""
    rows = ins["rel"]
    if rows.ndim != 2 or rows.shape[1] != len(COL):
        return np.empty((0, len(GROUPED)), np.int64)
    keys, inverse, counts = np.unique(rows[:, [COL.index(n) for n in GROUPS]], axis=0,
                                      return_inverse=True, return_counts=True)
    line = (rows[:, COL.index("l_extendedprice")]
            * (100 - rows[:, COL.index("l_discount")]))
    revenue = np.zeros(len(keys), np.int64)
    np.add.at(revenue, inverse.ravel(), line)
    return np.column_stack([keys, revenue, counts]).astype(np.int64)


def top(ins: Dict[str, np.ndarray], config: dict) -> np.ndarray:
    """The first ``limit`` rows by revenue descending, then o_orderdate, ties
    broken on o_orderkey.  Rows that are not the group-by's sort to
    nothing."""
    rows = ins["page_ids"]
    if rows.ndim != 2 or rows.shape[1] != len(GROUPED):
        return np.empty((0, len(GROUPED)), np.int64)
    order = np.lexsort([rows[:, GROUPED.index("o_orderkey")],
                        rows[:, GROUPED.index("o_orderdate")],
                        -rows[:, GROUPED.index("revenue")]])
    return rows[order[:parameters(config)["limit"]]]


def rows_in_order_differing(got: np.ndarray, want: np.ndarray) -> int:
    """Positions whose rows differ, plus the gap in length."""
    if not len(got) or not len(want) or got.shape[1:] != want.shape[1:]:
        return max(len(got), len(want))
    n = min(len(got), len(want))
    return int(np.count_nonzero((got[:n] != want[:n]).any(axis=1))) + abs(len(got) - len(want))


def rows_differing(got: np.ndarray, want: np.ndarray) -> int:
    """The multiset difference of ``check.rows_differing``; where one side
    is empty, the other side's rows."""
    if not len(got) or not len(want):
        return len(got) + len(want)
    return check.rows_differing(got, want)


REFERENCE = {"ehj": join, "eagg": aggregate, "ems": top}
CHECKS = {"ehj": "join_rows_differing", "eagg": "agg_groups_differing",
          "ems": "top_rows_differing"}
COMPARE = {"ehj": rows_differing, "eagg": rows_differing, "ems": rows_in_order_differing}
