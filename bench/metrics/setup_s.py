"""setup_s: seconds from the harness's start to the window's first
submit: imports, making the tables, planning, compiling or loading from
the compile cache, and the first query."""


def read(record):
    return record["setup_s"]
