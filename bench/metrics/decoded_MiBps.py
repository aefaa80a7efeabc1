"""decoded_MiBps: decoded bytes of every query completed in the window,
over the time from the window's start to the last completion (host clock).
The window closes at the first completion after its seconds, so all the
work is divided by all the time."""

MiB = 1 << 20


def read(run):
    done = [r for r in run.requests if r.done is not None and r.error is None]
    if not done:
        return None
    total = sum(run.system.decoded_bytes(r.item) for r in done)
    return total / MiB / (max(r.done for r in done) - run.t0)
