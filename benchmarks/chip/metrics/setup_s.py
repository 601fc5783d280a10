"""Process start to the first timed dispatch: imports, graph and stream
generation, ``from_graph`` with the device peel, program load or
compile, and the warm-up burst."""


def read(run):
    return run.setup_s
