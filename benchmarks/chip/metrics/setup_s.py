"""setup_s: from JAX's start-up done (runtime up, chips found) to the first
timed job: importing and building the cell's deployment, drawing and
staging its jobs on the device, and compiling or loading every program it
runs.  JAX's start-up itself is printed apart as ``init_s``."""


def read(run):
    return run.setup_s
