"""Set-up: process start to the window's start (loading, the weights,
warm-up and any compilation)."""


def read(rec):
    return rec["setup_s"]
