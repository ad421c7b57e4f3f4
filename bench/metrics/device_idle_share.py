"""Share of the traced window in which no operation ran on the device:
1 - (union of the device op intervals) / window, on the first chip."""
import devtrace


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["window_s"]:
        return None
    busy = devtrace.busy_ns(tr["events"]) * 1e-9
    return 100.0 * (1.0 - busy / tr["window_s"])
