"""Process start to ready to serve: JAX start-up, collection, index build,
executor warm-up."""


def read(run):
    return run.setup_s
