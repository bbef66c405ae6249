"""idle_in_program: share of the window in which a chip was idle while one
of the program's ``serving.*`` stage spans was open on the host, averaged
over the cell's chips, from the profiler trace: the idle time in which the
program's own stages held the chip back, apart from the idle time spent
waiting for requests.  A trace with no ``serving.*`` span reads nothing."""


def read(w):
    if w.trace is None or not w.trace["idle_with_stage_open_share"]:
        return None
    return 100.0 * w.trace["idle_in_program_share"]
