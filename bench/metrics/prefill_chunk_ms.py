"""Mean wall time of one chunked-prefill call, sync included: the window's
summed `Engine.stats` prefill_s over prefill_calls."""


def read(ctx):
    s = ctx["stats"]
    if not s.get("prefill_calls"):
        return None
    return 1e3 * s["prefill_s"] / s["prefill_calls"]
