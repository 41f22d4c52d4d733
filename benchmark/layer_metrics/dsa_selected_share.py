"""Model step: what the full layers' reads attend to of what their indexers
scored, over the window's decode steps: the program's
``dsa_tokens_selected`` / ``dsa_tokens_scored`` (``index_topk`` / the mean
live context; 1.0 would mean the read is dense)."""


def read(ctx):
    return ctx.window.get("dsa_selected_share")
