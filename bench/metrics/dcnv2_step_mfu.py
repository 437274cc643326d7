"""The DLRM-DCNv2 step's share of the chip's peak, in %.

The least time the step could take, the larger of its required FLOPs at
the peak bf16 rate and its required HBM bytes at the peak bandwidth
(``bench.work_dcnv2``), over the traced steps' wall time per step."""


def read(ctx):
    w, p = ctx["work"], ctx["peaks"]
    least = max(w.flops / p["bf16_flops_per_s"],
                w.step_bytes / p["hbm_bytes_per_s"])
    return 100.0 * least / ctx["step_s"]
