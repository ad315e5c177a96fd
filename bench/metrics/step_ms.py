"""The window's wall time over the steps completed in it."""


def read(run):
    done = run.attempted - run.failed
    return run.window_s / done * 1e3 if done else None
