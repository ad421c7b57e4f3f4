"""Mean host time of one decode step's dispatch: the jitted decode calls
returning (argument flattening, host-to-device copies, launch), the
`engine.decode.dispatch` span in `Engine.stats` over decode_steps."""


def read(ctx):
    s = ctx["stats"]
    if not s.get("decode_steps") or "engine_decode_dispatch_s" not in s:
        return None
    return 1e3 * s["engine_decode_dispatch_s"] / s["decode_steps"]
