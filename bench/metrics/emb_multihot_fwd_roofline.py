"""The multi-hot lookup's share of its HBM roofline, in %.

The lookup's required bytes (``bench.work_dcnv2``: each looked-up row
read once, the pooled outputs written once) at the peak HBM bandwidth,
over the device time of the ops under the program's ``emb.lookup``
scope (``bench.trace_scopes``).  Nothing where no op carries it."""


def read(ctx):
    from bench.trace_scopes import total
    busy = total(ctx.get("scopes") or {}, "emb.lookup")
    if busy <= 0:
        return None
    need = ctx["work"].lookup_bytes * ctx["steps"]
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / busy
