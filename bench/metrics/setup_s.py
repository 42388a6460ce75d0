"""``setup_s``: seconds from process start to the end of the warm-up
factorization: JAX start-up, compile or cache load, the data made on the
device, and one whole factorization."""


def read(run):
    if run.trace is not None:
        return None
    return run.setup_s
