"""The DCN-v2 cross network's share of its roofline, in %.

The cross layers' required FLOPs, forward and backward
(``bench.work_dcnv2``), at the peak bf16 rate, over the device time of
the ops under the program's ``dlrm.interact`` scope, forward and
backward (``bench.trace_scopes``).  Nothing where no op carries it."""


def read(ctx):
    from bench.trace_scopes import total
    busy = total(ctx.get("scopes") or {}, "dlrm.interact")
    if busy <= 0:
        return None
    need = ctx["work"].cross_flops * ctx["steps"]
    return 100.0 * need / ctx["peaks"]["bf16_flops_per_s"] / busy
