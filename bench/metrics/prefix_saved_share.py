"""KV arena: prompt tokens whose prefill the prefix index saved, as a
share of the prompt tokens of the requests the window admitted."""


def read(record):
    w = record["window"]
    key = "prefix_prefill_tokens_saved_total"
    saved = record["stats1"][key] - record["stats0"][key]
    prompt = sum(len(r.req.prompt) for r in w["recs"]
                 if r.req.admitted_at is not None)
    if not prompt:
        return None
    return 100.0 * saved / prompt
