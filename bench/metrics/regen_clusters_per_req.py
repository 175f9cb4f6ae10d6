"""Clusters regenerated by the encoder per request (the program's
``LatencyBreakdown.n_generated``), over the batches that started after the
traced part of the window."""


def read(w):
    if not w.n_requests:
        return None
    return sum(s.response.retrieval.n_generated for s in w.served) \
        / w.n_requests
