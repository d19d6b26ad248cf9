"""A number the harness took itself from outside the program (the load
generator's clock, the server process's CPU time): ``value`` names it."""


def read(ctx: dict, spec: dict):
    return ctx["observed"].get(spec["value"])
