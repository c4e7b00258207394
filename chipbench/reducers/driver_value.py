"""A number the driver already holds: a count it read from the program
(``counts``) or a time it took on its own clock (``values``)."""


def reduce(arguments, ctx, result, trace):
    key = arguments["key"]
    for group in ("counts", "values"):
        if result.get(group, {}).get(key) is not None:
            return result[group][key]
    return None
