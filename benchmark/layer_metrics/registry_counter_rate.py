"""The growth of one of the program's registry counters over the window,
per second (``"per": "second"``) or per unit of another counter
(``"per": "<series>"``: rows per batch is batch_rows per batches)."""


def read(args, reading):
    window = reading.result.registry
    grew = window.get(args["series"])
    if not isinstance(grew, (int, float)):
        return None
    if args["per"] == "second":
        return grew / reading.result.window_s
    base = window.get(args["per"])
    if not isinstance(base, (int, float)) or base <= 0:
        return None
    return grew / base
