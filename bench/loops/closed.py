"""A closed loop: one client issues the next request when the last one has
returned, to the items the mix's keys give, in order.  A request is due
when it is issued.  The window closes at the first completion after its
seconds, so the work of every request in it is divided by all its time.

Warm-up (``warmup.each_item``): every item once, so that every program the
window runs has been compiled or loaded from the cache.

The first ``generator.KEEP`` answers are kept for the comparison (every
one, in a window of fewer requests); later ones are dropped once they are
ready, so that a system that answers faster cannot fill the host's memory.
"""
import time

from bench.generator import KEEP, Request, span


def device_out(traffic: dict) -> bool:
    share = traffic["device_out_share"]
    if share not in (0, 1):
        raise ValueError(f"a closed loop takes device_out_share 0 or 1, "
                         f"not {share}")
    return bool(share)


def run(mix, system, seed: int, seconds: float) -> tuple:
    import jax
    dev = device_out(mix.traffic)
    n = len(system.items)
    order: list = []
    reqs = []
    t0 = time.perf_counter()
    while True:
        if not order:
            order = mix.keys(n, n, seed + len(reqs))
        r = Request(item=order.pop(0), device_out=dev,
                    due=time.perf_counter())
        try:
            with span("query.call"):
                out = system.call(r.item, r.device_out)
            r.sent = time.perf_counter()
            with span("query.wait"):
                r.out = jax.block_until_ready(out)
            r.done = time.perf_counter()
            if len(reqs) >= KEEP:
                r.out, r.kept = None, False
        except Exception as e:       # counted as failed, never raised
            r.error = e
        reqs.append(r)
        if time.perf_counter() - t0 >= seconds:
            return reqs, t0


def warm(mix, system, seed: int) -> int:
    import jax
    failed = 0
    if mix.traffic["warmup"].get("each_item"):
        for i in range(len(system.items)):
            try:
                jax.block_until_ready(
                    system.call(i, device_out(mix.traffic)))
            except Exception:
                failed += 1
    return failed
