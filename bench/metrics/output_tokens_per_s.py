"""Output tokens that callers received in the window, over its length."""


def read(run):
    toks = sum(r.out_tokens for r in run.requests
               if r.ok and r.done is not None and r.done <= run.window_s)
    return toks / run.window_s
