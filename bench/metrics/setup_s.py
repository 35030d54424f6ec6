"""Set-up: process start to window start (generation, clustering and
build, warm-up, compilation or cache loads)."""


def read(rec):
    return rec["setup_s"]
