"""A count the run recorded (``params["key"]``), as it is."""


def read(record, params):
    return record.get(params["key"])
