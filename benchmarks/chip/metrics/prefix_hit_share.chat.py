"""Share of prompt tokens served from the prefix cache, over the window's
requests: sum of the engine's ``cached_prompt_tokens`` over sum of prompt
tokens, in percent."""


def read(run):
    done = [q for q in run["requests"] if q["tokens"]]
    total = sum(q["prompt_len"] for q in done)
    return 100.0 * sum(q["cached"] for q in done) / total if total else None
