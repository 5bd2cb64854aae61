"""1 - union of device-operation intervals over the traced stretch."""


def read(ctx):
    trace = ctx["trace"]
    return None if trace is None else 100.0 * trace["idle_share"]
