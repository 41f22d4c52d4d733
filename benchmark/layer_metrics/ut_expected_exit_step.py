"""Model step: the pass (0 .. total_ut_steps - 1) at which the model's own
exit gate expects to stop, mean over the window's decoded tokens: the
program's ``ut_exit_step_milli`` (the sum over live rows of ``round(1000 x
sum_u u p_u)``, counted in the decode block) / 1000 / decoded tokens.
Nothing acts on it at ``early_exit_threshold`` 1: it is what an adaptive
exit would have to beat, and it shows that the gate is computed."""


def read(ctx):
    return ctx.window.get("ut_expected_exit_step")
