"""Test fixture: a later PR's reducer."""


def reduce(ctx):
    return ctx.facts.get("fits")
