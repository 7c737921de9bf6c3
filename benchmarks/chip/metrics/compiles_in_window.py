"""Backend compilations inside the window, persistent-cache loads
included."""


def read(run):
    return float(run.compiles)
