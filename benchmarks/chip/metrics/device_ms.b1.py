"""Device busy time in the traced window over the requests answered in
it, in ms: the chip's own time per single-image request."""


def read(run):
    t = run.trace_summary
    if t is None or not run.window.done:
        return None
    return 1e3 * t["busy_s"] / len(run.window.done)
