"""Host milliseconds per request in ``RAGEngine.stage_plan`` (S1: centroid
probe and tier plan; span ``s1.plan``), over the batches that started after
the traced part of the window."""


def read(w):
    return w.per_request_ms("s1.plan")
