"""Process start to window start (s): loading, weights, compiling or
reading compiled programs, warm-up and any prompt filled in set-up."""


def read(run):
    return run.t0 - run.t_process
