"""Process start to the first timed step: JAX start-up, plan build (the
Green's function), compilation or cache loads, right-hand sides made on
the device, warm-up steps."""


def read(run):
    return run.setup_s
