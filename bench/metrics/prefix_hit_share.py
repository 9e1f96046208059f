"""Share (%) of the window's prompt tokens served from the prefix cache:
the engines' ``prefix_tokens_reused`` over the prompt tokens admitted."""


def read(run):
    prompt = sum(r.prompt_len for r in run.requests if r.ok)
    if not prompt or "prefix_tokens_reused" not in run.engine:
        return None
    return 100.0 * run.engine["prefix_tokens_reused"] / prompt
