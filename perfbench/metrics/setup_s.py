"""setup_s: process start to the first timed request (host clock): start-up,
corpus or cluster set-up, warm-up and any compilation."""


def read(run):
    return run["setup_s"]
