"""TPC-H data-generator connector.

Conceptual parity with presto-tpch (reference presto-tpch/src/main/java/io/
prestosql/plugin/tpch/TpchConnectorFactory.java, TpchMetadata.java,
TpchRecordSetProvider wrapping io.airlift.tpch generators), re-designed for
vectorized device-feeding: every column is a pure stateless-hash function of
the row's primary key (splitmix64), so any split can generate any row range
with full referential consistency (l_extendedprice really is quantity *
p_retailprice(l_partkey), lineitem dates derive from the parent order's
orderdate) and no cross-table reads — the generator is embarrassingly
parallel across splits and hosts.

Distributions follow the TPC-H spec shapes (selectivities match within
sampling noise; e.g. Q6's date/discount/quantity predicate selects ~2%).
Exact dbgen bit-compatibility is NOT a goal: correctness tests compare
against an oracle computed over this same data.

Low-cardinality columns carry *stable dictionaries* (compile-friendly);
formatted/unique names and comments are per-batch text columns.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .. import types as T
from ..batch import Batch, Schema
from .spi import (
    ColumnStats, Connector, ConnectorMetadata, ConnectorSplitManager,
    PageSource, Split, TableHandle, TableStats,
)

# Epoch-day constants (see spec 4.2.3)
START_DATE = 8035        # 1992-01-01
END_ORDERDATE = 10440    # 1998-08-02
CURRENT_DATE = 9298      # 1995-06-17
ORDERDATE_SPAN = END_ORDERDATE - START_DATE + 1

_U64 = np.uint64
_GOLDEN = _U64(0x9E3779B97F4A7C15)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = (x + _GOLDEN).astype(_U64)
    x = ((x ^ (x >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)).astype(_U64)
    x = ((x ^ (x >> _U64(27))) * _U64(0x94D049BB133111EB)).astype(_U64)
    return (x ^ (x >> _U64(31))).astype(_U64)


def _h(key: np.ndarray, tag: int) -> np.ndarray:
    """Per-column hash stream over a key array."""
    tag_mix = _U64((tag * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF)
    k = key.astype(_U64) ^ tag_mix
    return _splitmix64(k)


def _randint(key, tag, lo, hi) -> np.ndarray:
    """Uniform integers in [lo, hi] as int64."""
    h = _h(key, tag)
    span = _U64(hi - lo + 1)
    # add in int64: NumPy 2 (NEP 50) raises OverflowError mixing a negative
    # python int with a uint64 array
    return np.int64(lo) + (h % span).astype(np.int64)


def _money(key, tag, lo, hi) -> np.ndarray:
    """Uniform price with 2 decimal digits, as double."""
    cents = _randint(key, tag, int(lo * 100), int(hi * 100))
    return cents.astype(np.float64) / 100.0


# -- word lists (spec appendix; abbreviated but spec-shaped) -----------------

SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
PRIORITIES = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW")
INSTRUCTS = ("DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
ORDER_STATUS = ("F", "O", "P")
RETURN_FLAGS = ("A", "N", "R")
LINE_STATUS = ("O", "F")
TYPE_S1 = ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
TYPE_S2 = ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
TYPE_S3 = ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")
P_TYPES = tuple(f"{a} {b} {c}" for a in TYPE_S1 for b in TYPE_S2 for c in TYPE_S3)
CONTAINER_S1 = ("SM", "LG", "MED", "JUMBO", "WRAP")
CONTAINER_S2 = ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM")
CONTAINERS = tuple(f"{a} {b}" for a in CONTAINER_S1 for b in CONTAINER_S2)
MFGRS = tuple(f"Manufacturer#{i}" for i in range(1, 6))
BRANDS = tuple(f"Brand#{i}{j}" for i in range(1, 6) for j in range(1, 6))
P_NAME_WORDS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush".split()
    + "brown burlywood burnished chartreuse chiffon chocolate coral cornflower".split()
    + "cornsilk cream cyan dark deep dim dodger drab firebrick floral".split()
    + "forest frosted gainsboro ghost goldenrod green grey honeydew hot indian".split()
    + "ivory khaki lace lavender lawn lemon light lime linen magenta".split()
    + "maroon medium metallic midnight mint misty moccasin navajo navy olive".split()
    + "orange orchid pale papaya peach peru pink plum powder puff".split()
    + "purple red rose rosy royal saddle salmon sandy seashell sienna".split()
    + "sky slate smoke snow spring steel tan thistle tomato turquoise".split()
    + "violet wheat white yellow".split()
)
NATIONS = (
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
)
REGIONS = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")
COMMENT_WORDS = (
    "furiously quickly carefully slyly blithely final express regular special "
    "pending unusual ironic even bold silent fluffy ruthless idle busy daring "
    "deposits requests accounts packages instructions theodolites foxes ideas "
    "pinto beans dependencies excuses platelets asymptotes courts dolphins "
    "sleep nag haggle wake dazzle cajole boost detect engage integrate"
).split()


def _pick(key, tag, values: Tuple[str, ...]) -> np.ndarray:
    """Enum column: int32 codes into a stable dictionary."""
    return (_h(key, tag) % _U64(len(values))).astype(np.int32)


def _comment(key, tag, nwords=4) -> List[str]:
    idx = [(_h(key, tag * 97 + i) % _U64(len(COMMENT_WORDS))).astype(np.int64)
           for i in range(nwords)]
    w = np.asarray(COMMENT_WORDS, dtype=object)
    parts = [w[i] for i in idx]
    out = parts[0]
    for p in parts[1:]:
        out = out + " " + p
    return list(out)


def _p_name(key) -> List[str]:
    w = np.asarray(P_NAME_WORDS, dtype=object)
    parts = [w[(_h(key, 300 + i) % _U64(len(P_NAME_WORDS))).astype(np.int64)]
             for i in range(5)]
    out = parts[0]
    for p in parts[1:]:
        out = out + " " + p
    return list(out)


def _phone(key, tag, nationkey) -> List[str]:
    a = 10 + nationkey
    b = _randint(key, tag + 1, 100, 999)
    c = _randint(key, tag + 2, 100, 999)
    d = _randint(key, tag + 3, 1000, 9999)
    return [f"{ai}-{bi}-{ci}-{di}" for ai, bi, ci, di in zip(a, b, c, d)]


def _retailprice(partkey: np.ndarray) -> np.ndarray:
    # spec 4.2.3: (90000 + ((partkey/10) mod 20001) + 100*(partkey mod 1000))/100
    pk = partkey.astype(np.int64)
    return (90000 + (pk // 10) % 20001 + 100 * (pk % 1000)) / 100.0


def _supplier_of_part(partkey, i, scale_suppliers):
    # spec 4.2.3 partsupp.suppkey formula: spreads each part's 4 suppliers
    pk = partkey.astype(np.int64)
    s = scale_suppliers
    return (pk + i * (s // 4 + (pk - 1) // s)) % s + 1


# -- per-table row counts ----------------------------------------------------

def _rows(table: str, sf: float) -> int:
    base = {
        "customer": 150_000, "orders": 1_500_000, "part": 200_000,
        "supplier": 10_000, "partsupp": 800_000,
        "nation": 25, "region": 5,
    }
    if table == "lineitem":
        # ~4 lines per order on average (exact count derived per split)
        return int(6_000_000 * sf)
    if table in ("nation", "region"):
        return base[table]
    return int(base[table] * sf)


# -- schemas (types match presto-tpch defaults: DOUBLE prices) ---------------

V = T.VARCHAR
_SCHEMAS: Dict[str, List[Tuple[str, T.Type]]] = {
    "lineitem": [
        ("l_orderkey", T.BIGINT), ("l_partkey", T.BIGINT),
        ("l_suppkey", T.BIGINT), ("l_linenumber", T.INTEGER),
        ("l_quantity", T.DOUBLE), ("l_extendedprice", T.DOUBLE),
        ("l_discount", T.DOUBLE), ("l_tax", T.DOUBLE),
        ("l_returnflag", T.varchar(1)), ("l_linestatus", T.varchar(1)),
        ("l_shipdate", T.DATE), ("l_commitdate", T.DATE),
        ("l_receiptdate", T.DATE), ("l_shipinstruct", T.varchar(25)),
        ("l_shipmode", T.varchar(10)), ("l_comment", T.varchar(44)),
    ],
    "orders": [
        ("o_orderkey", T.BIGINT), ("o_custkey", T.BIGINT),
        ("o_orderstatus", T.varchar(1)), ("o_totalprice", T.DOUBLE),
        ("o_orderdate", T.DATE), ("o_orderpriority", T.varchar(15)),
        ("o_clerk", T.varchar(15)), ("o_shippriority", T.INTEGER),
        ("o_comment", T.varchar(79)),
    ],
    "customer": [
        ("c_custkey", T.BIGINT), ("c_name", T.varchar(25)),
        ("c_address", T.varchar(40)), ("c_nationkey", T.BIGINT),
        ("c_phone", T.varchar(15)), ("c_acctbal", T.DOUBLE),
        ("c_mktsegment", T.varchar(10)), ("c_comment", T.varchar(117)),
    ],
    "part": [
        ("p_partkey", T.BIGINT), ("p_name", T.varchar(55)),
        ("p_mfgr", T.varchar(25)), ("p_brand", T.varchar(10)),
        ("p_type", T.varchar(25)), ("p_size", T.INTEGER),
        ("p_container", T.varchar(10)), ("p_retailprice", T.DOUBLE),
        ("p_comment", T.varchar(23)),
    ],
    "supplier": [
        ("s_suppkey", T.BIGINT), ("s_name", T.varchar(25)),
        ("s_address", T.varchar(40)), ("s_nationkey", T.BIGINT),
        ("s_phone", T.varchar(15)), ("s_acctbal", T.DOUBLE),
        ("s_comment", T.varchar(101)),
    ],
    "partsupp": [
        ("ps_partkey", T.BIGINT), ("ps_suppkey", T.BIGINT),
        ("ps_availqty", T.INTEGER), ("ps_supplycost", T.DOUBLE),
        ("ps_comment", T.varchar(199)),
    ],
    "nation": [
        ("n_nationkey", T.BIGINT), ("n_name", T.varchar(25)),
        ("n_regionkey", T.BIGINT), ("n_comment", T.varchar(152)),
    ],
    "region": [
        ("r_regionkey", T.BIGINT), ("r_name", T.varchar(25)),
        ("r_comment", T.varchar(152)),
    ],
}

TABLES = tuple(_SCHEMAS)


def _orders_orderdate(okey: np.ndarray) -> np.ndarray:
    return START_DATE + (_h(okey, 5) % _U64(ORDERDATE_SPAN)).astype(np.int64)


def _lines_per_order(okey: np.ndarray) -> np.ndarray:
    return 1 + (_h(okey, 100) % _U64(7)).astype(np.int64)


class _Gen:
    """Column generators. Each returns (np storage array, dictionary|None)
    given the key array (primary key / row id, 1-based)."""

    def __init__(self, sf: float):
        self.sf = sf
        self.n_cust = _rows("customer", sf)
        self.n_part = _rows("part", sf)
        self.n_supp = _rows("supplier", sf)
        self.n_orders = _rows("orders", sf)

    # ---- orders ----
    def orders(self, key: np.ndarray, cols: Sequence[str]):
        out = {}
        odate = _orders_orderdate(key)
        for c in cols:
            if c == "o_orderkey":
                out[c] = (key.astype(np.int64), None)
            elif c == "o_custkey":
                ck = 1 + (_h(key, 1) % _U64(self.n_cust)).astype(np.int64)
                # spec: a third of customers never place orders
                ck = np.where(ck % 3 == 0, np.maximum(ck - 1, 1), ck)
                out[c] = (ck, None)
            elif c == "o_orderstatus":
                # F = all lines shipped (old orders), O = none (recent), P = mixed
                code = np.where(odate + 182 < CURRENT_DATE, 0,
                                np.where(odate > CURRENT_DATE, 1, 2))
                out[c] = (code.astype(np.int32), ORDER_STATUS)
            elif c == "o_totalprice":
                out[c] = (_money(key, 3, 1000.0, 500000.0), None)
            elif c == "o_orderdate":
                out[c] = (odate.astype(np.int32), None)
            elif c == "o_orderpriority":
                out[c] = (_pick(key, 6, PRIORITIES), PRIORITIES)
            elif c == "o_clerk":
                n = max(1, int(1000 * self.sf))
                ids = 1 + (_h(key, 7) % _U64(n)).astype(np.int64)
                out[c] = ([f"Clerk#{i:09d}" for i in ids], "text")
            elif c == "o_shippriority":
                out[c] = (np.zeros(len(key), dtype=np.int32), None)
            elif c == "o_comment":
                out[c] = (_comment(key, 8, 5), "text")
            else:
                raise KeyError(c)
        return out

    # ---- lineitem (key = orderkey*8 + linenumber) ----
    def lineitem(self, okey: np.ndarray, ln: np.ndarray, cols: Sequence[str]):
        key = (okey.astype(np.int64) * 8 + ln).astype(np.int64)
        odate = _orders_orderdate(okey)
        out = {}
        partkey = 1 + (_h(key, 11) % _U64(self.n_part)).astype(np.int64)
        quantity = 1 + (_h(key, 13) % _U64(50)).astype(np.int64)
        shipdate = odate + 1 + (_h(key, 17) % _U64(121)).astype(np.int64)
        receipt = shipdate + 1 + (_h(key, 19) % _U64(30)).astype(np.int64)
        for c in cols:
            if c == "l_orderkey":
                out[c] = (okey.astype(np.int64), None)
            elif c == "l_partkey":
                out[c] = (partkey, None)
            elif c == "l_suppkey":
                i = (_h(key, 12) % _U64(4)).astype(np.int64)
                out[c] = (_supplier_of_part(partkey, i, self.n_supp), None)
            elif c == "l_linenumber":
                out[c] = ((ln + 1).astype(np.int32), None)
            elif c == "l_quantity":
                out[c] = (quantity.astype(np.float64), None)
            elif c == "l_extendedprice":
                out[c] = (quantity * _retailprice(partkey), None)
            elif c == "l_discount":
                out[c] = ((_h(key, 14) % _U64(11)).astype(np.float64) / 100.0, None)
            elif c == "l_tax":
                out[c] = ((_h(key, 15) % _U64(9)).astype(np.float64) / 100.0, None)
            elif c == "l_returnflag":
                r = (_h(key, 16) % _U64(2)).astype(np.int32)  # A or R
                code = np.where(receipt <= CURRENT_DATE, r * 2, 1)  # N else
                out[c] = (code.astype(np.int32), RETURN_FLAGS)
            elif c == "l_linestatus":
                out[c] = (np.where(shipdate > CURRENT_DATE, 0, 1).astype(np.int32),
                          LINE_STATUS)
            elif c == "l_shipdate":
                out[c] = (shipdate.astype(np.int32), None)
            elif c == "l_commitdate":
                commit = odate + 30 + (_h(key, 18) % _U64(61)).astype(np.int64)
                out[c] = (commit.astype(np.int32), None)
            elif c == "l_receiptdate":
                out[c] = (receipt.astype(np.int32), None)
            elif c == "l_shipinstruct":
                out[c] = (_pick(key, 20, INSTRUCTS), INSTRUCTS)
            elif c == "l_shipmode":
                out[c] = (_pick(key, 21, MODES), MODES)
            elif c == "l_comment":
                out[c] = (_comment(key, 22, 3), "text")
            else:
                raise KeyError(c)
        return out

    # ---- customer ----
    def customer(self, key: np.ndarray, cols: Sequence[str]):
        out = {}
        nation = (_h(key, 31) % _U64(25)).astype(np.int64)
        for c in cols:
            if c == "c_custkey":
                out[c] = (key.astype(np.int64), None)
            elif c == "c_name":
                out[c] = ([f"Customer#{i:09d}" for i in key], "text")
            elif c == "c_address":
                out[c] = (_comment(key, 32, 3), "text")
            elif c == "c_nationkey":
                out[c] = (nation, None)
            elif c == "c_phone":
                out[c] = (_phone(key, 33, nation), "text")
            elif c == "c_acctbal":
                out[c] = (_money(key, 34, -999.99, 9999.99), None)
            elif c == "c_mktsegment":
                out[c] = (_pick(key, 35, SEGMENTS), SEGMENTS)
            elif c == "c_comment":
                out[c] = (_comment(key, 36, 6), "text")
            else:
                raise KeyError(c)
        return out

    # ---- part ----
    def part(self, key: np.ndarray, cols: Sequence[str]):
        out = {}
        for c in cols:
            if c == "p_partkey":
                out[c] = (key.astype(np.int64), None)
            elif c == "p_name":
                out[c] = (_p_name(key), "text")
            elif c == "p_mfgr":
                m = (_h(key, 41) % _U64(5)).astype(np.int32)
                out[c] = (m, MFGRS)
            elif c == "p_brand":
                # brand within mfgr: Brand#MJ
                m = (_h(key, 41) % _U64(5)).astype(np.int64)
                j = (_h(key, 42) % _U64(5)).astype(np.int64)
                out[c] = ((m * 5 + j).astype(np.int32), BRANDS)
            elif c == "p_type":
                out[c] = (_pick(key, 43, P_TYPES), P_TYPES)
            elif c == "p_size":
                out[c] = (_randint(key, 44, 1, 50).astype(np.int32), None)
            elif c == "p_container":
                out[c] = (_pick(key, 45, CONTAINERS), CONTAINERS)
            elif c == "p_retailprice":
                out[c] = (_retailprice(key), None)
            elif c == "p_comment":
                out[c] = (_comment(key, 46, 2), "text")
            else:
                raise KeyError(c)
        return out

    # ---- supplier ----
    def supplier(self, key: np.ndarray, cols: Sequence[str]):
        out = {}
        nation = (_h(key, 51) % _U64(25)).astype(np.int64)
        for c in cols:
            if c == "s_suppkey":
                out[c] = (key.astype(np.int64), None)
            elif c == "s_name":
                out[c] = ([f"Supplier#{i:09d}" for i in key], "text")
            elif c == "s_address":
                out[c] = (_comment(key, 52, 3), "text")
            elif c == "s_nationkey":
                out[c] = (nation, None)
            elif c == "s_phone":
                out[c] = (_phone(key, 53, nation), "text")
            elif c == "s_acctbal":
                out[c] = (_money(key, 54, -999.99, 9999.99), None)
            elif c == "s_comment":
                # spec: some suppliers have "Customer Complaints"/"Recommends"
                base = _comment(key, 55, 5)
                h = _h(key, 56) % _U64(2000)
                txt = [
                    ("Customer Complaints " + b) if hi < 10 else
                    ("Customer Recommends " + b) if hi < 20 else b
                    for b, hi in zip(base, h)
                ]
                out[c] = (txt, "text")
            else:
                raise KeyError(c)
        return out

    # ---- partsupp (key = row id 1..4*n_part) ----
    def partsupp(self, key: np.ndarray, cols: Sequence[str]):
        out = {}
        pk = 1 + (key.astype(np.int64) - 1) // 4
        i = (key.astype(np.int64) - 1) % 4
        for c in cols:
            if c == "ps_partkey":
                out[c] = (pk, None)
            elif c == "ps_suppkey":
                out[c] = (_supplier_of_part(pk, i, self.n_supp), None)
            elif c == "ps_availqty":
                out[c] = (_randint(key, 61, 1, 9999).astype(np.int32), None)
            elif c == "ps_supplycost":
                out[c] = (_money(key, 62, 1.0, 1000.0), None)
            elif c == "ps_comment":
                out[c] = (_comment(key, 63, 6), "text")
            else:
                raise KeyError(c)
        return out

    # ---- nation / region (tiny, fixed) ----
    def nation(self, key: np.ndarray, cols: Sequence[str]):
        out = {}
        names = tuple(n for n, _ in NATIONS)
        for c in cols:
            if c == "n_nationkey":
                out[c] = (key.astype(np.int64) - 1, None)
            elif c == "n_name":
                out[c] = ((key - 1).astype(np.int32), names)
            elif c == "n_regionkey":
                rk = np.asarray([NATIONS[int(k) - 1][1] for k in key], dtype=np.int64)
                out[c] = (rk, None)
            elif c == "n_comment":
                out[c] = (_comment(key, 71, 4), "text")
            else:
                raise KeyError(c)
        return out

    def region(self, key: np.ndarray, cols: Sequence[str]):
        out = {}
        for c in cols:
            if c == "r_regionkey":
                out[c] = (key.astype(np.int64) - 1, None)
            elif c == "r_name":
                out[c] = ((key - 1).astype(np.int32), REGIONS)
            elif c == "r_comment":
                out[c] = (_comment(key, 72, 4), "text")
            else:
                raise KeyError(c)
        return out


def _to_batch(schema: Schema, cols: Sequence[str], data: Dict, n: int,
              distinct_text: Sequence[str] = ()) -> Batch:
    arrays, dicts = [], []
    out_schema = schema.select(list(cols))
    for name in cols:
        arr, vocab = data[name]
        if vocab == "text" and name in distinct_text:
            # a value a row, as the deployment states: the rows'
            # positions are the codes, the strings the vocabulary
            if len(set(arr)) != n:
                raise ValueError(f"{name} is stated distinct row by row "
                                 f"and holds a value twice")
            arrays.append(np.arange(n, dtype=np.int32))
            dicts.append(tuple(arr))
        elif vocab == "text":
            # per-batch vocabulary for free-text columns
            uniq: Dict[str, int] = {}
            codes = np.empty(n, dtype=np.int32)
            for i, s in enumerate(arr):
                code = uniq.get(s)
                if code is None:
                    code = uniq[s] = len(uniq)
                codes[i] = code
            arrays.append(codes)
            dicts.append(tuple(uniq))
        elif vocab is not None:
            arrays.append(arr)
            dicts.append(tuple(vocab))
        else:
            arrays.append(arr)
            dicts.append(None)
    return Batch.from_arrays(out_schema, arrays, None, dicts, num_rows=n)


class TpchPageSource(PageSource):
    def __init__(self, gen: _Gen, split: Split, columns: Sequence[str],
                 rows_per_batch: int, distinct_text: Sequence[str] = ()):
        self.gen = gen
        self.split = split
        self.columns = list(columns)
        self.rows_per_batch = rows_per_batch
        self.distinct_text = tuple(distinct_text)

    def host_chunks(self):
        """(schema, generated column dict, row count) per chunk, host-side
        only — lets callers that want host arrays (bench staging, oracles)
        skip the device round trip."""
        table = self.split.table.table
        schema = tpch_schema(table)
        if table == "lineitem":
            o_start, o_end = self.split.info
            a = o_start
            # whole orders: at most a quarter of rows_per_batch of them
            # (4.0 lines an order on average: a group-by by order key
            # then holds a chunk's groups in a quarter of the lanes,
            # chunk after chunk the same bucket), and no more than fit
            # rows_per_batch LINES: the quarter alone left every other
            # chunk a few lines over 2^20, staged at 2^21 lanes, half of
            # them dead (91M lanes for SF10's 60M lines)
            step = max(1, self.rows_per_batch // 4)
            while a < o_end:
                okeys = np.arange(a, min(a + step, o_end), dtype=np.int64)
                counts = _lines_per_order(okeys)
                fit = max(1, int(np.searchsorted(
                    np.cumsum(counts), self.rows_per_batch, side="right")))
                okeys, counts = okeys[:fit], counts[:fit]
                a += fit
                rep_ok = np.repeat(okeys, counts)
                ln = np.arange(len(rep_ok)) - np.repeat(
                    np.cumsum(counts) - counts, counts)
                data = self.gen.lineitem(rep_ok, ln, self.columns)
                yield schema, data, len(rep_ok)
            return
        start, end = self.split.info
        genfn = getattr(self.gen, table)
        for a in range(start, end, self.rows_per_batch):
            b = min(a + self.rows_per_batch, end)
            keys = np.arange(a, b, dtype=np.int64)
            yield schema, genfn(keys, self.columns), b - a

    def batches(self) -> Iterator[Batch]:
        for schema, data, n in self.host_chunks():
            yield _to_batch(schema, self.columns, data, n,
                            self.distinct_text)


def tpch_schema(table: str) -> Schema:
    return Schema(_SCHEMAS[table])


class _Metadata(ConnectorMetadata):
    def __init__(self, sf: float, tables: Sequence[str],
                 clustered_by: Optional[Dict[str, Sequence[str]]] = None):
        self.sf = sf
        self.tables = tuple(tables)
        self.clustered_by = {t: tuple(c) for t, c in
                             (clustered_by or {}).items()}

    def list_tables(self, schema: Optional[str] = None) -> List[str]:
        return list(self.tables)

    def table_schema(self, table: TableHandle) -> Schema:
        if table.table not in self.tables:
            raise KeyError(f"unknown tpch table {table.table!r}")
        return tpch_schema(table.table)

    _PRIMARY_KEYS = {
        "lineitem": ("l_orderkey", "l_linenumber"),
        "orders": ("o_orderkey",),
        "customer": ("c_custkey",),
        "part": ("p_partkey",),
        "supplier": ("s_suppkey",),
        "partsupp": ("ps_partkey", "ps_suppkey"),
        "nation": ("n_nationkey",),
        "region": ("r_regionkey",),
    }

    def table_stats(self, table: TableHandle) -> TableStats:
        t = table.table
        n = float(_rows(t, self.sf))
        cols: Dict[str, ColumnStats] = {}
        if t == "lineitem":
            n_orders = _rows("orders", self.sf)
            cols["l_orderkey"] = ColumnStats(n_orders, 0.0, 1, n_orders)
            cols["l_partkey"] = ColumnStats(
                _rows("part", self.sf), 0.0, 1, _rows("part", self.sf))
            cols["l_suppkey"] = ColumnStats(
                _rows("supplier", self.sf), 0.0, 1,
                _rows("supplier", self.sf))
            cols["l_linenumber"] = ColumnStats(7, 0.0, 1, 7)
            cols["l_shipdate"] = ColumnStats(ORDERDATE_SPAN + 151, 0.0, START_DATE, END_ORDERDATE + 151)
            cols["l_discount"] = ColumnStats(11, 0.0, 0.0, 0.10)
            cols["l_tax"] = ColumnStats(9, 0.0, 0.0, 0.08)
            cols["l_quantity"] = ColumnStats(50, 0.0, 1.0, 50.0)
        if t == "orders":
            cols["o_orderkey"] = ColumnStats(n, 0.0, 1, int(n))
            cols["o_custkey"] = ColumnStats(
                _rows("customer", self.sf), 0.0, 1,
                _rows("customer", self.sf))
            cols["o_orderdate"] = ColumnStats(ORDERDATE_SPAN, 0.0, START_DATE, END_ORDERDATE)
        # dimension key bounds are EXACT from the generator (sequential
        # 1..n keys; nation/region domains fixed by spec) — hard bounds,
        # so the planner may select dense-key direct-address joins
        # (optimizer._attach_join_strategy) and stats-bounded grouping
        # on these columns, same contract as TpcdsConnector.table_stats
        if t == "customer":
            cols["c_custkey"] = ColumnStats(n, 0.0, 1, int(n))
            cols["c_nationkey"] = ColumnStats(25, 0.0, 0, 24)
        if t == "part":
            cols["p_partkey"] = ColumnStats(n, 0.0, 1, int(n))
            cols["p_size"] = ColumnStats(50, 0.0, 1, 50)
        if t == "supplier":
            cols["s_suppkey"] = ColumnStats(n, 0.0, 1, int(n))
            cols["s_nationkey"] = ColumnStats(25, 0.0, 0, 24)
        if t == "partsupp":
            cols["ps_partkey"] = ColumnStats(
                _rows("part", self.sf), 0.0, 1, _rows("part", self.sf))
            cols["ps_suppkey"] = ColumnStats(
                _rows("supplier", self.sf), 0.0, 1,
                _rows("supplier", self.sf))
        if t == "nation":
            cols["n_nationkey"] = ColumnStats(25, 0.0, 0, 24)
            cols["n_regionkey"] = ColumnStats(5, 0.0, 0, 4)
        if t == "region":
            cols["r_regionkey"] = ColumnStats(5, 0.0, 0, 4)
        for pk in self._PRIMARY_KEYS.get(t, ()):
            if pk not in cols:
                cols[pk] = ColumnStats(distinct_count=n if len(self._PRIMARY_KEYS[t]) == 1 else None)
        return TableStats(row_count=n, columns=cols,
                          primary_key=self._PRIMARY_KEYS.get(t, ()),
                          clustered_by=self.clustered_by.get(t, ()))


class _SplitManager(ConnectorSplitManager):
    def __init__(self, sf: float):
        self.sf = sf

    def splits(self, table: TableHandle, desired: int = 1) -> List[Split]:
        t = table.table
        if t == "lineitem":
            n = _rows("orders", self.sf)
        else:
            n = _rows(t, self.sf)
        desired = max(1, min(desired, n))
        bounds = np.linspace(1, n + 1, desired + 1, dtype=np.int64)
        return [
            Split(table, (int(bounds[i]), int(bounds[i + 1])))
            for i in range(desired)
            if bounds[i] < bounds[i + 1]
        ]


class TpchConnector(Connector):
    """catalog 'tpch', schema names are scale factors ('sf1', 'tiny'...)."""

    name = "tpch"
    applies_pushdown = False    # page_source drops it

    def __init__(self, sf: float = 0.01, tables: Sequence[str] = TABLES,
                 distinct_text: Sequence[str] = (),
                 clustered_by: Optional[Dict[str, Sequence[str]]] = None):
        """``tables``: the tables this catalog holds (a deployment of
        one fact table lists it alone); every TPC-H table by default.
        ``distinct_text``: text columns of which the deployment states
        that every row holds another value (``c_name`` is
        ``Customer#<c_custkey>``): a batch of such a column takes its
        rows' positions for codes and its strings for vocabulary, and
        no Python loop looks each string up for a repeat.
        ``clustered_by``: {table: columns} of which the deployment
        states that the table's rows are generated in those columns'
        order (every table here is generated in its primary key's
        order, lineitem by ``l_orderkey`` then ``l_linenumber``): the
        statistics then say so (``TableStats.clustered_by``), a
        group-by over them compiles no sort, and a batch out of order
        fails the query."""
        for t, cols in (clustered_by or {}).items():
            pk = _Metadata._PRIMARY_KEYS.get(t, ()) if t in tables else ()
            if not cols or tuple(cols) != pk[:len(cols)]:
                raise ValueError(
                    f"tpch {t!r} is not generated clustered by "
                    f"{list(cols)} (its order is {list(pk)})")
        unknown = sorted(set(tables) - set(TABLES))
        if unknown:
            raise ValueError(f"unknown tpch tables {unknown}")
        known = {n for t in tables for n, ty in _SCHEMAS[t] if ty.is_string}
        unknown = sorted(set(distinct_text) - known)
        if unknown:
            raise ValueError(f"no text columns {unknown} in {list(tables)}")
        self.distinct_text = tuple(distinct_text)
        self.sf = sf
        self._metadata = _Metadata(sf, tables, clustered_by)
        self._splits = _SplitManager(sf)
        self._gen = _Gen(sf)

    def data_version(self, table: str):
        # stateless generator: any split regenerates identically for the
        # connector's whole lifetime, so the device scan cache may hold it
        return 0

    @property
    def metadata(self) -> ConnectorMetadata:
        return self._metadata

    @property
    def split_manager(self) -> ConnectorSplitManager:
        return self._splits

    def page_source(self, split: Split, columns: Sequence[str],
                    pushdown=None, rows_per_batch: int = 1 << 17) -> PageSource:
        return TpchPageSource(self._gen, split, columns, rows_per_batch,
                              self.distinct_text)
