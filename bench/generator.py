"""The one traffic generator: a mix from ``bench/traffic/<mix>.json``.

A mix is data.  Its keys:

* ``loop``: how requests are issued; the harness finds the loop by name as
  ``bench/loops/<loop>.py``;
* ``keys``: ``{"kind": k, ...}``, which items the requests go to; found by
  name as ``bench/keys/<k>.py``, and its other keys are that module's;
* ``select``: handed to the configuration, which returns the items;
* ``device_out_share``: the share of requests that want the result in HBM
  (the rest want host arrays);
* ``warmup``: read by the loop: what set-up runs, so that the window
  compiles nothing.

A loop module has ``run(mix, system, seed, seconds) -> (requests, t0)`` and
``warm(mix, system, seed) -> failed``.  A keys module has ``draw(params,
n_items, count, seed) -> item indices``; every seed draws the same multiset
of items, in an order of its own.  A mix that needs another loop or key
choice adds a module; nothing here names one.

Host spans (``jax.profiler.TraceAnnotation``, names in ``SPANS``) say what
the loop is doing, so that the trace reduction can name what the host did
while the device idled.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

SPANS = ("query.call", "query.wait")
KEEP = 16      # answers kept for the comparison; later ones are dropped


@dataclasses.dataclass
class Request:
    item: int                     # index into the system's items
    device_out: bool
    due: float                    # perf_counter seconds
    sent: Optional[float] = None  # when the call returned
    done: Optional[float] = None  # when the result was ready
    out: Any = None
    error: Optional[BaseException] = None
    placed: bool = False          # where the request asked for it
    kept: bool = True             # its answer is kept for the comparison


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Mix:
    traffic: dict
    loop: Any        # the module bench/loops/<loop>.py
    key_choice: Any  # the module bench/keys/<kind>.py

    def keys(self, n_items: int, count: int, seed: int) -> list:
        return [int(k) for k in self.key_choice.draw(
            self.traffic["keys"], n_items, count, seed)]

    def run(self, system, seed: int, seconds: float) -> tuple:
        """The measured window: ``(requests, t0)``."""
        return self.loop.run(self, system, seed, seconds)

    def warm(self, system, seed: int) -> int:
        """The mix's warm-up; how many warm-up requests failed."""
        return self.loop.warm(self, system, seed)
