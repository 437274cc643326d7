"""The whole step's share of the chips' peak, in %.

The least time the step could take on the cell's chips, the larger of
its required FLOPs at the peak bf16 rate and its required HBM bytes at
the peak bandwidth (``bench.work``), over the traced steps' wall time
per step.  ``bound`` says which of the two is larger.
"""


def least_time(ctx):
    """(seconds, "flops" or "bytes") for one step on all the chips."""
    p, w, n = ctx["peaks"], ctx["work"], ctx["chips"]
    compute = w.flops / (n * p["bf16_flops_per_s"])
    memory = w.step_bytes / (n * p["hbm_bytes_per_s"])
    return (compute, "flops") if compute >= memory else (memory, "bytes")


def read(ctx):
    return 100.0 * least_time(ctx)[0] / ctx["step_s"]
