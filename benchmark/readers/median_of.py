"""Median of a list the driver recorded (``params["key"]``)."""

import statistics


def read(record, params):
    values = record.get(params["key"])
    return statistics.median(values) if values else None
