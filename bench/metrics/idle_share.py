"""Percent of the time the serving loop works on a batch (host spans
``bench.batch``) in which no operation runs on the device, in the traced
part of the window."""
from bench import trace


def read(w):
    return None if w.trace is None else trace.idle_share(w.trace)
