"""Share of the traced window in which no op runs on the device, in %,
on the chip where it is largest (the multi-hot step cell)."""


def read(ctx):
    return 100.0 * ctx["summary"].worst_idle_share()
