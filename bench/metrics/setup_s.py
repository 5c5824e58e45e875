"""Set-up seconds: from the run's start (JAX import included) to the end
of warm-up: making the rows, the index build, its upload, compilation
or cache loads, and warm-up traffic."""


def read(run):
    return run.setup_s
