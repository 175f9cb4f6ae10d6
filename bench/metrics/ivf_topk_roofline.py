"""Share of its roofline that the centroid probe kernel (``ivf_topk``)
reached in the traced part of the window: the least time its operations
and bytes need on the chip over its device time."""
from bench import flops

KERNEL = r"topk_ip_pallas"


def read(w):
    if w.trace is None or w.peak is None:
        return None
    seconds = w.kernel_seconds(KERNEL)
    if not seconds:
        return None
    d = w.config["encoder"]["hidden_size"]
    f = b = 0
    for q_embs, _ in w.probes[:w.traced_batches]:
        fi, bi = flops.ivf_topk(len(q_embs), w.config["nlist"], d)
        f, b = f + fi, b + bi
    return flops.roofline_share(f, b, seconds, w.peak)
