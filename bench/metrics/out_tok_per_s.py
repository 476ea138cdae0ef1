"""Generated tokens that reached the host in the window, over the window."""


def read(run):
    return sum(r.tokens_in_window for r in run.all_requests) / run.seconds
