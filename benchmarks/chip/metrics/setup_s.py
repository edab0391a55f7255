"""Seconds from the start of ``run.py`` to the first timed step: imports,
TPU start, weights and data from the seed, compile or cache load and
warm-up."""


def read(rec):
    return rec["setup_s"]
