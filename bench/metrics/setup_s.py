"""Process start to the first timed request (host clock): loading, weight
generation, compiling or reading the compile cache, and the warm-up."""


def read(run):
    return run.t_setup_end - run.t_process
