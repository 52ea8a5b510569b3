"""Process start to the first timed round: JAX and TPU start-up, data,
engine build, the initial state, and the two set-up chunks (the first
compiles, or loads the compiled chunk from the persistent cache)."""


def read(run):
    return run.setup_s
