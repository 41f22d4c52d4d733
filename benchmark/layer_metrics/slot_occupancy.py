"""Engine: share of decode-block slot-steps that emitted a token, over the
window (``engine.decode_tokens`` / ``engine.slot_steps`` deltas)."""


def read(ctx):
    return ctx.window.get("slot_occupancy")
