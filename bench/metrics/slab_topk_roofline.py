"""Share of its roofline that the packed-slab scoring kernel
(``slab_topk``) reached in the traced part of the window."""
from bench import flops

KERNEL = r"slab_topk_pallas"


def read(w):
    if w.trace is None or w.peak is None:
        return None
    seconds = w.kernel_seconds(KERNEL)
    if not seconds:
        return None
    d = w.config["encoder"]["hidden_size"]
    f = b = 0
    for rows_per_q, unique in w.slab_work[:w.traced_batches]:
        fi, bi = flops.slab_topk(d, w.config["k"], rows_per_q, unique)
        f, b = f + fi, b + bi
    return flops.roofline_share(f, b, seconds, w.peak)
