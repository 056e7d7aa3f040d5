"""End to end: process start (``run.py``'s first statement) to the first timed
operation: imports, data from the seed, upload, server start, warm-up of this
seed's own shapes, compilation or the fetch of every program from the cache."""


def read(facts: dict):
    return facts["setup_s"]
