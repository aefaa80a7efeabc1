"""host_call_ms.scan: median per query of the host time from the call into
the program until it returns, before waiting for the device (host clock)."""

import statistics


def read(run):
    calls = [r.sent - r.due for r in run.requests if r.sent is not None]
    return statistics.median(calls) * 1e3 if calls else None
