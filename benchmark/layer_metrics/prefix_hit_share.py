"""Engine: share of the prompt tokens admitted in the window that were
served from the prefix index instead of prefilled (``engine.shared_tokens``
/ ``engine.prompt_tokens`` deltas)."""


def read(ctx):
    return ctx.window.get("prefix_hit_share")
