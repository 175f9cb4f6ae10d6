"""Host milliseconds per request in ``RAGEngine.stage_score`` (S3: slab
pack, slab top-k, prompt assembly; span ``s3.score``), over the batches that
started after the traced part of the window."""


def read(w):
    return w.per_request_ms("s3.score")
