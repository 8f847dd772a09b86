"""Kernels: the least time the chip's memory could take to read the
columns the statement scans (the rows of each table, as the
configuration states them, x the width of each queried column, from
the template's own table; over the peak HBM bandwidth) as a share of
the device's busy seconds per traced query. Bytes-bound, and the same
work whatever implements the query."""


def scan_bytes(template, tables: dict) -> int:
    return sum(tables[table] * sum(cols.values())
               for table, cols in template.SCAN_COLUMNS.items())


def read(run):
    t = run["trace"]
    if not t or not t["busy_s"] or not run["peaks"]:
        return None
    cell = run["cell"]
    least = scan_bytes(cell.template, cell.config["tables"]) \
        / (run["peaks"]["hbm_gbytes_per_s"] * 1e9)
    return 100.0 * least / (t["busy_s"] / t["queries"])
