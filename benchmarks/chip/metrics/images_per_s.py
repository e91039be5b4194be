"""Images answered in the window over the window's length (host clock)."""


def read(run):
    w = run.window
    return len(w.done) / w.seconds if w.done else None
