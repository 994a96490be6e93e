"""A number the job worked out itself, by the job's key for it."""


def read(args, reading):
    return reading.result.values.get(args["key"])
