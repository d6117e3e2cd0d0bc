"""Prompt tokens the engine prefilled in the window over the window's
seconds: all the prefill work and all the time of the window, decode
ticks included (``drive.Window.prefill_tokens``)."""


def read(ctx):
    return ctx.prefill_tokens / ctx.seconds
