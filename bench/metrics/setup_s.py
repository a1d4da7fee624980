"""Process start to the window's opening: imports, weights, folding,
quantization and route tables, compiles or cache loads, warm-up of every
bucket, and the warm-up traffic before the window."""


def read(run):
    return run.setup_s
