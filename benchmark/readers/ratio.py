"""One counter's delta over another's (``params``: num, den, scale).
A denominator that did not move: nothing to read."""


def read(record, params):
    counters = record.get("counters", {})
    den = counters.get(params["den"], 0.0)
    if den <= 0 or params["num"] not in counters:
        return None
    return params.get("scale", 1.0) * counters[params["num"]] / den
