"""Share of the engine's wall time spent outside its jitted step calls:
1 - (prefill_s + decode_s) / wall_s, from `Engine.stats` summed over the
window's rounds (each step time ends in a host sync)."""


def read(ctx):
    s = ctx["stats"]
    if not s.get("wall_s"):
        return None
    return 100.0 * (1.0 - (s["prefill_s"] + s["decode_s"]) / s["wall_s"])
