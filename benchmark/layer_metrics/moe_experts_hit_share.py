"""Model step: share of the routed experts held that received at least one
token, mean over the window's decode steps and expert layers (the
program's ``moe_expert_hits`` / (steps x expert layers x experts held)).
What a step must read of the expert weights."""


def read(ctx):
    return ctx.window.get("moe_experts_hit_share")
