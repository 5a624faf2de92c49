"""A number the runner measured itself, by key."""


def read(ctx, params):
    return ctx["values"].get(params["key"])
