"""The whole model step's share of the chip's peak over the window: the
operations the model needs for the tokens the window processed (prompt
tokens prefilled, generated tokens after the first decoded), each kind at
the peak of the precision it runs in, over the window's wall time.

Projections are the planned layers, 2 x fan_in x fan_out per row (the LM
head only at each request's last prompt position and at decoded tokens),
their int8 columns at the int8 peak and the rest at the bf16 peak; an LM
head tied to the embeddings is no planned layer and runs in bf16;
attention and state-space work is the reference module's `mixer_flops` at
the bf16 peak."""


def _mixer(ctx, start, count):
    """Sum of mixer flops over positions start .. start + count - 1."""
    f = ctx["reference"].mixer_flops
    a, b = f(ctx["sizes"], 0), f(ctx["sizes"], 1)
    slope = b - a
    return count * a + slope * (count * start + count * (count - 1) / 2)


def read(ctx):
    if ctx["peaks"] is None or not ctx["done"]:
        return None
    prompt = sum(len(ctx["prompts"][r.rid]) for r in ctx["done"])
    decode = sum(len(r.tokens) - 1 for r in ctx["done"])
    int8 = bf16 = 0.0
    for name, _, k, n, int8_share in ctx["layers"]:
        rows = len(ctx["done"]) if name == "head" else prompt
        uses = ctx["reference"].layer_uses(ctx["sizes"], name)
        ops = 2.0 * k * n * uses * (rows + decode)
        int8 += ops * int8_share
        bf16 += ops * (1.0 - int8_share)
    if ctx["sizes"].get("tie_embeddings"):
        bf16 += 2.0 * ctx["sizes"]["d_model"] * ctx["sizes"]["vocab"] * \
            (len(ctx["done"]) + decode)
    mixer = 0.0
    for r in ctx["done"]:
        p = len(ctx["prompts"][r.rid])
        mixer += _mixer(ctx, 0, p) + _mixer(ctx, p, len(r.tokens) - 1)
    pk = ctx["peaks"]
    busy = int8 / pk["int8_op_s"] + (bf16 + mixer) / pk["bf16_flop_s"]
    return 100.0 * busy / ctx["window_s"]
