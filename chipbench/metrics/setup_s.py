"""From the start of the process to the first timed step: imports, weights
from the seed, compilation or the compile cache, warm-up and the first
steps the comparison reads."""


def read(rec):
    return rec["setup_s"]
