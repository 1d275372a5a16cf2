"""End to end: seconds from process start to the start of the window."""


def read(record):
    return record["setup_seconds"]
