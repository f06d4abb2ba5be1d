"""Output tokens emitted inside the window over the window's length, for the
whole cell (all its chips). tokens/s."""


def read(run):
    length = run.t_close - run.t_open
    return run.tokens_in_window / length if length > 0 and run.tokens_in_window else None
