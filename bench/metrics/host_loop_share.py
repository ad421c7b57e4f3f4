"""Share of the engine's wall time in which the host was not blocked on
the device: 1 - (engine_chunk_wait_s + engine_decode_wait_s) / wall_s,
from the engine's phase spans in `Engine.stats`, summed over the window's
rounds.  The program's counterpart of `device_idle_share`."""


def read(ctx):
    s = ctx["stats"]
    if not s.get("wall_s") or "engine_decode_wait_s" not in s:
        return None
    wait = s.get("engine_chunk_wait_s", 0.0) + s["engine_decode_wait_s"]
    return 100.0 * (1.0 - wait / s["wall_s"])
