"""A count or harness-clock timing the run itself kept (``ctx.facts``)."""


def read(ctx, key, scale=1.0):
    value = ctx.facts.get(key)
    return None if value is None else value * scale
