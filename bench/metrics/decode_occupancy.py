"""Share of the decode slots that decode calls ran: the engine's
decode_rows counter (active slots of each call, summed) over decode_steps
x max_batch."""


def read(ctx):
    s = ctx["stats"]
    if not s.get("decode_steps") or "decode_rows" not in s:
        return None
    slots = s["decode_steps"] * ctx["mix"]["engine"]["max_batch"]
    return 100.0 * s["decode_rows"] / slots
