"""90th percentile over completed requests of the wait from becoming
schedulable to the first admission into a slot (`RequestResult.queue_s`),
the part of time to first token spent queued."""
import stats


def read(ctx):
    waits = [getattr(r, "queue_s", None) for r in ctx["done"]]
    if not waits or None in waits:
        return None
    return 1e3 * stats.percentile(waits, 90)
