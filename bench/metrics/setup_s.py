"""Seconds from the process's start to the start of the arrival pump's
clock: imports, weights, scheduler, warm-up, every compile or cache
load, and a backlog's opening admissions.  A pre-roll is traffic, not
set-up, and is left out."""


def read(run):
    return run.setup_s
