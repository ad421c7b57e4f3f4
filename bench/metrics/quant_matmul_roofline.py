"""Roofline share of the planned int8 matmul kernel (`quant_matmul`): the
least time its calls in the window could take over the device time of its
kernel events in the trace.

The least time is counted per planned layer and per kind of call, chunked
prefill and decode, as max(operations / int8 peak, bytes / HBM bandwidth)
of that layer's calls together, with the operations and bytes of the valid
rows only (`roofline.quant_matmul_cost`): prompt tokens in chunk calls (one
row per request for the LM head, which reads each slot's last position) and
generated tokens after the first in decode calls.  Padded and masked rows
count as waste.  A layer whose weights several blocks share counts once per
use (the reference module's `layer_uses`).  A kernel event is a custom call whose name stack holds
``quant_matmul``."""
import roofline
import devtrace

NEEDLE = "quant_matmul"
KERNEL = "quant_matmul"


def least_seconds(ctx) -> float:
    s, peaks = ctx["stats"], ctx["peaks"]
    prompt = sum(len(ctx["prompts"][r.rid]) for r in ctx["done"]) - \
        s.get("prefix_hit_tokens", 0)
    decode = sum(len(r.tokens) - 1 for r in ctx["done"])
    total = 0.0
    for name, kernel, k, n, _ in ctx["layers"]:
        if kernel != KERNEL:
            continue
        rows = len(ctx["done"]) if name == "head" else prompt
        uses = ctx["reference"].layer_uses(ctx["sizes"], name)
        for calls, m in ((uses * s["prefill_calls"], uses * rows),
                         (uses * s["decode_steps"], uses * decode)):
            if not calls:
                continue
            ops, nbytes = roofline.quant_matmul_cost(m, k, n)
            # weights and column steps are read once per call
            nbytes += (calls - 1) * (k * n + 4 * n)
            total += roofline.least_seconds(ops, nbytes, peaks)
    return total


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["peaks"] is None:
        return None
    ns, count = devtrace.kernel_ns(tr["events"], NEEDLE)
    if not count:
        return None
    return 100.0 * least_seconds(ctx) / (ns * 1e-9)
