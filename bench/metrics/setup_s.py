"""End to end: process start to the first timed request: imports, inputs,
compilation or reads from the persistent cache, and warm-up."""


def read(rec: dict):
    return rec["setup_s"]
