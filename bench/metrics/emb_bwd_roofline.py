"""Embedding backward and row-wise update's share of its HBM roofline,
in %.

The least time the chips could take to move the backward's required
bytes (``bench.work``: the pooled gradients read once, each distinct
touched row and its accumulator entry read and written once) at the peak
HBM bandwidth, over the device time of the ops under the transposed
``bench_emb_lookup`` scope and the ``bench_emb_update`` scope, summed
over the chips.  Nothing when no op carries the scopes.
"""


def read(ctx):
    busy = ctx["summary"].scope_s("emb_bwd")
    if busy <= 0:
        return None
    need = ctx["work"].emb_bwd_bytes * ctx["steps"]
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / busy
