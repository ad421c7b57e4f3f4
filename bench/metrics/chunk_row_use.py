"""Share of the rows that chunked-prefill calls compute which hold a prompt
token: every call computes max_batch x prefill_chunk rows whoever is
prefilling.  Prompt tokens served from the prefix cache are not computed."""


def read(ctx):
    s, eng = ctx["stats"], ctx["mix"]["engine"]
    if not s.get("prefill_calls"):
        return None
    tokens = sum(len(ctx["prompts"][r.rid]) for r in ctx["done"]) - \
        s.get("prefix_hit_tokens", 0)
    rows = s["prefill_calls"] * eng["max_batch"] * eng["prefill_chunk"]
    return 100.0 * tokens / rows
