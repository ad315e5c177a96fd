"""Host clock around the first solve: compilation, or loading from the
persistent compile cache, and that solve's run."""


def read(run):
    return run.compile_s
