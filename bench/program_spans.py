"""Readings of the program's own spans (``repro.core.tracing``) in the
traced part of a window.

While a profiler trace is collected, every program span enters
``jax.profiler.TraceAnnotation`` under its bare name, so the trace's host
plane holds it beside the benchmark's spans, on the profiler's clock, and
``bench.trace.Trace.host`` keeps it (its prefixes are in ``HOST_PREFIXES``).
These readers take the spans that lie inside the window.  A program that
opens no such span leaves the reading None.

Requests are counted by their ``s4.prefill`` spans: one per generated
answer.
"""
from __future__ import annotations

import statistics
from typing import List, Optional, Tuple


def spans(w, name: str) -> List[Tuple[float, float]]:
    """The window's ``name`` spans, in order of their start."""
    if w.trace is None:
        return []
    w0, w1 = w.trace.window
    return sorted((s, e) for s, e, n in w.trace.host
                  if n == name and s >= w0 and e <= w1)


def _requests(w) -> int:
    return len(spans(w, "s4.prefill"))


def per_request_ms(w, *names: str,
                   outside: Optional[str] = None) -> Optional[float]:
    """Milliseconds per request in the spans ``names``, leaving out those
    that lie inside a span whose name starts with ``outside``."""
    n = _requests(w)
    ivs = [iv for name in names for iv in spans(w, name)]
    if not n or not ivs:
        return None
    if outside is not None:
        fences = [(s, e) for s, e, name in w.trace.host
                  if name.startswith(outside)]
        ivs = [(s, e) for s, e in ivs
               if not any(a <= s and e <= b for a, b in fences)]
    return 1e3 * sum(e - s for s, e in ivs) / n


def mean_ms(w, name: str) -> Optional[float]:
    ivs = spans(w, name)
    if not ivs:
        return None
    return 1e3 * statistics.fmean(e - s for s, e in ivs)


def first_token_ms(w) -> Optional[float]:
    """Median over requests of the end of its ``s4.prefill`` (its first
    token on the host) less the start of its batch's S1 (``s1.stage``,
    the first stage ``answer_batch`` runs)."""
    starts = [s for s, _ in spans(w, "s1.stage")]
    ttft = []
    for s, e in spans(w, "s4.prefill"):
        before = [b for b in starts if b <= s]
        if before:
            ttft.append(e - before[-1])
    return 1e3 * statistics.median(ttft) if ttft else None
