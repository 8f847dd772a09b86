"""TPC-H Q1, the pricing-summary report: one scan of lineitem, a dense
low-cardinality group-by (return flag x line status), eight DOUBLE
aggregates and a count.

The SQL text is the benchmark's own (Presto's
``sql/presto/tpch/q01.sql`` with a named hole). ``reference`` is plain
NumPy over the benchmark's own data and shares nothing with the program.
"""
import datetime

import numpy as np

SQL = """\
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
  sum(l_extendedprice) as sum_base_price,
  sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
  sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
  avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
  avg(l_discount) as avg_disc, count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '{DELTA}' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus"""

#: TPC-H specification clause 2.4.1.3, written from memory
ASSUMED = {"DELTA": "days, drawn from 60..120"}

KINDS = ("str", "str") + ("double",) * 7 + ("int",)

#: between the lower reading 1.47e-14 (the program on the chip, SF10, a
#: dozen seeds) and the upper 2.87e-7 (the float32 control, the same
#: seeds), the more room above the lower (my chip run, PR 25)
DOUBLE_REL_LIMIT = 1e-10

SCAN_COLUMNS = {"lineitem": {"l_returnflag": 4, "l_linestatus": 4,
                             "l_quantity": 8, "l_extendedprice": 8,
                             "l_discount": 8, "l_tax": 8, "l_shipdate": 4}}


def draw(rng) -> dict:
    return {"DELTA": str(rng.randint(60, 120))}


def reference(data, sf: float, bindings, float_type=np.float64) -> list:
    ft = float_type
    end = (datetime.date(1998, 12, 1) - datetime.date(1970, 1, 1)).days
    cutoffs = [end - int(b["DELTA"]) for b in bindings]
    n_ls = len(data.LINE_STATUS)
    n_groups = len(data.RETURN_FLAGS) * n_ls

    def part(li):
        qty, price, disc, tax = (li[c].astype(ft) for c in (
            "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
        dp = price * (ft(1) - disc)
        ch = dp * (ft(1) + tax)
        group = li["l_returnflag"] * n_ls + li["l_linestatus"]
        out = []
        for cutoff in cutoffs:
            keep = li["l_shipdate"] <= cutoff
            acc = np.zeros((n_groups, 5), dtype=ft)
            cnt = np.zeros(n_groups, dtype=np.int64)
            for g in range(n_groups):
                m = keep & (group == g)
                cnt[g] = m.sum()
                if cnt[g]:
                    acc[g] = [c[m].sum(dtype=ft)
                              for c in (qty, price, dp, ch, disc)]
            out.append((acc, cnt))
        return out

    parts = data.map_lineitem(part, sf)
    answers = []
    for i in range(len(bindings)):
        acc = np.zeros((n_groups, 5), dtype=ft)
        cnt = np.zeros(n_groups, dtype=np.int64)
        for p in parts:
            acc = (acc + p[i][0]).astype(ft)
            cnt += p[i][1]
        rows = []
        for g in range(n_groups):
            if not cnt[g]:
                continue
            n = ft(cnt[g])
            s = acc[g]
            rows.append((data.RETURN_FLAGS[g // n_ls],
                         data.LINE_STATUS[g % n_ls],
                         float(s[0]), float(s[1]), float(s[2]), float(s[3]),
                         float(s[0] / n), float(s[1] / n), float(s[4] / n),
                         int(cnt[g])))
        rows.sort(key=lambda r: (r[0], r[1]))
        answers.append(rows)
    return answers
