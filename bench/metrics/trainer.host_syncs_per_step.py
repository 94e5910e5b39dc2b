"""Blocking device-to-host fetches of ``Trainer.fit`` per traced step
(``Trainer.host_fetches``, an exact count)."""


def read(ctx):
    if not ctx.steps:
        return None
    return ctx.host_fetches / ctx.steps
