"""Host milliseconds per request in ``RAGEngine.stage_fetch`` (S2: cache,
storage or regeneration; span ``s2.fetch``), over the batches that started
after the traced part of the window."""


def read(w):
    return w.per_request_ms("s2.fetch")
