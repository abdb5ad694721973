"""Process start -> the first event of the window due."""


def read(record):
    return record.setup["setup_s"]
