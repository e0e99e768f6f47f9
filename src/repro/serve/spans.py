"""Host spans of the serving path.

One mechanism gives both readings of a block: a
``jax.profiler.TraceAnnotation`` (kept by the profiler only while a trace
runs, on the device trace's clock) and the block's ``time.perf_counter``
duration, added to ``rec[key]`` when a record is given.  Spans are always
on; nothing is written out, the durations live in the records the caller
keeps (``SampledBatch.meta``, ``HGNNServeEngine.step_log``).

Span names, outermost first::

    hgnn.serve.step            one slot-loop step (arg ``step``)
      hgnn.serve.refill        expiry, refill, chunking
      hgnn.serve.sample        the sampler call (retry, prefetch)
        hgnn.sample            HGNNSampler.sample
          hgnn.sample.expand   frontier expansion, order, rung choice
          hgnn.sample.gather   local tables, relabel, feature rows
            hgnn.sample.upload one array's host-to-device transfer
      hgnn.forward             dispatch through the logits on the host
      hgnn.serve.scatter       rows into request buffers, finalize
    hgnn.infer                 HGNNInferEngine.infer (dispatch only)
"""
from __future__ import annotations

import time
from typing import Dict, Optional

from jax.profiler import TraceAnnotation


class span:
    """``with span(name, rec, key):`` -- see the module docstring.

    ``args`` become the trace event's arguments, and are encoded only
    while a trace is active.  ``t0`` is the block's start on the
    ``perf_counter`` clock."""

    __slots__ = ("_ann", "_rec", "_key", "t0")

    def __init__(self, name: str, rec: Optional[Dict] = None,
                 key: Optional[str] = None, **args):
        self._ann = (TraceAnnotation(name, **args)
                     if args and TraceAnnotation.is_enabled()
                     else TraceAnnotation(name))
        self._rec = rec
        self._key = key

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._rec is not None:
            dt = time.perf_counter() - self.t0
            self._rec[self._key] = self._rec.get(self._key, 0.0) + dt
        self._ann.__exit__(*exc)
