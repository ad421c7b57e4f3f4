"""The decode calls' share of the HBM roofline: their least bytes at the
chip's HBM bandwidth, over the device time of the decode calls in the trace.

Least bytes: the cached keys and values their attention read (the engine's
decode_kv_tokens counter: each active slot's cache length after the step,
times the attention layers a token passes, times the reference module's
`kv_bytes_per_token`), each call's weights (the planned layers with int8
columns one byte and the rest two, and a tied LM head in bfloat16) and the
active slots' recurrent state, read and written once a call (decode_rows
times the reference's `state_bytes_per_slot`, twice).

Device time: the window's device ops fall into engine calls, runs of ops
with no idle gap longer than GAP_NS between them (each call ends in a host
sync, and the host dispatches the next only after it, which leaves the
device idle for longer).  A call is a decode call when it runs
``quant_matmul`` kernels and none of them on more rows than the engine's
batch (a chunk call runs its projections on batch x chunk rows).  The
reading is the least seconds over the decode calls' busy device seconds.
Silent where the engine has no such counter, the reference no such sizes,
or the calls found are not the engine's decode_steps."""
import re

NEEDLE = "quant_matmul"
GAP_NS = 50_000.0
_ROWS = re.compile(r"= \w+\[(\d+)")


def least_bytes(ctx):
    """Least HBM bytes of the window's decode calls, or None where they
    cannot be counted."""
    s, ref, sz = ctx["stats"], ctx["reference"], ctx["sizes"]
    if not s.get("decode_steps") or "decode_kv_tokens" not in s or \
            not hasattr(ref, "kv_bytes_per_token"):
        return None
    weights = sum(k * n * (2.0 - int8_share)
                  for _, _, k, n, int8_share in ctx["layers"])
    if sz.get("tie_embeddings"):
        weights += 2.0 * sz["d_model"] * sz["vocab"]
    kv = s["decode_kv_tokens"] * ref.kv_bytes_per_token(sz)
    state = 2.0 * s["decode_rows"] * ref.state_bytes_per_slot(sz)
    return kv + weights * s["decode_steps"] + state


def kernel_rows(name: str):
    """Rows of a ``quant_matmul`` kernel event's result, None for any other
    event."""
    lhs = name.split(" = ", 1)[0]
    if NEEDLE not in lhs or " custom-call(" not in name:
        return None
    m = _ROWS.search(name)
    return int(m.group(1)) if m else None


def calls(events, batch: int):
    """[(kind, busy ns)] of the engine calls in ``events``, in time order;
    kind is "decode", "chunk" or "other" (no planned kernel)."""
    out = []
    end, busy, rows = None, 0.0, []
    for e in sorted(events, key=lambda e: e.start_ns):
        if end is not None and e.start_ns - end > GAP_NS:
            out.append((busy, rows))
            end, busy, rows = None, 0.0, []
        if end is None:
            end = e.start_ns
        busy += max(0.0, e.end_ns - max(e.start_ns, end))
        end = max(end, e.end_ns)
        r = kernel_rows(e.name)
        if r is not None:
            rows.append(r)
    if end is not None:
        out.append((busy, rows))
    kind = lambda rows: ("other" if not rows else
                         "decode" if max(rows) <= batch else "chunk")
    return [(kind(rows), busy) for busy, rows in out]


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["peaks"] is None:
        return None
    need = least_bytes(ctx)
    if need is None:
        return None
    decode = [ns for kind, ns in calls(tr["events"],
                                       ctx["mix"]["engine"]["max_batch"])
              if kind == "decode"]
    if len(decode) != ctx["stats"]["decode_steps"]:
        return None
    seconds = need / ctx["peaks"]["hbm_byte_s"]
    return 100.0 * seconds / (sum(decode) * 1e-9)
