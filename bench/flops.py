"""Operations and bytes the kernels need, from shapes; the chip's peaks.
(A model's FLOPs are its architecture's, in ``bench/archs``.)

The counts are of the work the algorithm asks for, the same whatever
implements it: padding, masks and layout copies are left out, so a change
that removes them shows as a higher share of the roofline and not as a new
count.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> Dict[str, float]:
    """The peak table's row for a device kind; an unknown kind raises."""
    table = json.loads(Path(path).read_text())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


def ivf_topk(q: int, nlist: int, d: int):
    """(flops, bytes) of one centroid probe: Q queries against nlist
    float32 centroids."""
    return 2 * q * nlist * d, 4 * (nlist * d + q * d)


def slab_topk(d: int, k: int, rows_per_query: Sequence[int],
              unique_rows: int):
    """(flops, bytes) of one slab launch: each query scores the rows it
    probes; the unique probed float32 rows, the queries and the (score,
    row) outputs cross memory once."""
    q = len(rows_per_query)
    return (2 * d * sum(rows_per_query),
            4 * (unique_rows * d + q * d) + 8 * q * k)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: Dict[str, float]) -> float:
    """Least time the chip could take (the larger of the compute and the
    memory bound) over the measured time, in percent."""
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
