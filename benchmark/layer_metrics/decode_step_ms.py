"""Model step: device time of the decode-block program's executions in the
trace ("XLA Modules" line, by module name) per decode step."""


def read(ctx):
    mod = ctx.trace_summary.get("modules", {}).get(
        ctx.window.get("decode_module"))
    if not mod or not mod[0]:
        return None
    runs, seconds = mod
    return seconds / (runs * ctx.window["decode_block"]) * 1e3
