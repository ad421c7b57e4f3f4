"""Mean wall time of one decode step call, sync included: the window's
summed `Engine.stats` decode_s over decode_steps."""


def read(ctx):
    s = ctx["stats"]
    if not s.get("decode_steps"):
        return None
    return 1e3 * s["decode_s"] / s["decode_steps"]
