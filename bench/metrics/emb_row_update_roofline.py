"""The touched-row embedding update's share of its HBM roofline, in %.

The update's required bytes (``bench.work_dcnv2``: the pooled gradients
read once, each distinct touched row and its accumulator read and
written once) at the peak HBM bandwidth, over the device time of the
ops under the program's ``dlrm.emb_update`` scope, which holds the
update's sort (``emb.bwd.sort``), fetch (``emb.bwd.fetch``) and row
writes (``emb.update.rows``) (``bench.trace_scopes``).  Nothing where
no op carries ``emb.update.rows``."""


def read(ctx):
    from bench.trace_scopes import total
    scopes = ctx.get("scopes") or {}
    busy = total(scopes, "dlrm.emb_update")
    if total(scopes, "emb.update.rows") <= 0 or busy <= 0:
        return None
    need = ctx["work"].update_bytes * ctx["steps"]
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / busy
