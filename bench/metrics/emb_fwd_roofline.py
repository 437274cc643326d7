"""Embedding forward's share of its HBM roofline, in %.

The least time the chips could take to move the forward's required bytes
(``bench.work``: each live looked-up row read once, the pooled outputs
written once) at the peak HBM bandwidth, over the device time of the ops
under the ``bench_emb_lookup`` scope, all-to-alls left out, summed over
the chips.  Nothing when no op carries the scope.
"""


def read(ctx):
    busy = ctx["summary"].scope_s("emb_fwd")
    if busy <= 0:
        return None
    need = ctx["work"].emb_fwd_bytes * ctx["steps"]
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / busy
