"""Device: share of the traced window in which no operation ran on the chip,
in a cell that serves block writes."""


def read(ctx):
    tr = ctx.get("trace")
    return tr.idle_pct if tr is not None and tr.devices else None
