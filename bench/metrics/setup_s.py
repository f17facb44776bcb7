"""Process start to the first due request: imports, device start, weights,
engine build and warm-up, the harness's warm-up traffic."""


def read(run):
    return run.setup_s
