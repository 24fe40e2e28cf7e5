"""A number the harness measured itself, by its key."""


def read(args: dict, ctx: dict) -> float | None:
    return ctx["harness"].get(args["key"])
