"""Kernels: device milliseconds a traced query in the programs only a
residual semi join launches, the ``breakdown.device_ops`` rows named by
``PREFIXES``: the probe of either form (``jit_expr_semi_keyed_*``,
``jit_expr_semi_expand_*``: lookup, payload gather and residual in one
program a batch) and the packing of a unique build's payload
(``jit_op_pack_sorted_payload``). The summary's group-by runs as
``jit_op_grouped_aggregate*`` and the build's table as
``jit_op_prepare_*``, names other operators launch too: not counted
here. Only the TEN busiest programs of the traced slice are seen, which
is what the reduction keeps: a row that falls out of the ten reads as
0 of this sum. None without a trace or where no such row is among the
ten."""

PREFIXES = ("jit_expr_semi_keyed_", "jit_expr_semi_expand_",
            "jit_op_pack_sorted_payload")


def read(run):
    t = run["trace"]
    if not t or not t.get("queries") or not t.get("device_ops"):
        return None
    rows = [secs for name, secs in t["device_ops"]
            if name.startswith(PREFIXES)]
    if not rows:
        return None
    return 1e3 * sum(rows) / t["queries"]
