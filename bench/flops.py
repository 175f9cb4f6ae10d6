"""Operations and bytes the algorithm needs, from shapes; the chip's peaks.

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


def non_embedding_params(m: dict) -> int:
    """Parameters a token's forward pass multiplies by, per token: the
    block stack (norms included) and the final norm; the output head is
    counted apart, since only decoded positions use it."""
    d, q = m["hidden_size"], m["num_heads"] * m["head_dim"]
    kv, ff = m["num_kv_heads"] * m["head_dim"], m["intermediate_size"]
    return m["num_layers"] * (d * (q + 2 * kv) + q * d + 2 * d + 3 * d * ff) + d


def encoder_flops(m: dict, lengths: Sequence[int]) -> float:
    """Forward FLOPs of the bidirectional encoder over rows of ``lengths``
    real tokens: 2 x parameters per token plus attention (scores and
    weighted sum, 2 x 2 x ctx x q_dim per layer per token)."""
    n, qd, L = non_embedding_params(m), m["num_heads"] * m["head_dim"], \
        m["num_layers"]
    return float(sum(2 * n * t + 4 * L * qd * t * t for t in lengths))


def generator_flops(m: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs of one causal generation: the prompt's real tokens,
    then the ``new_tokens - 1`` generated tokens fed back; token i attends
    to i + 1 positions; the output head runs once per generated token."""
    n, qd, L = non_embedding_params(m), m["num_heads"] * m["head_dim"], \
        m["num_layers"]
    total = prompt_len + new_tokens - 1
    attn = 4 * L * qd * total * (total + 1) / 2
    head = 2 * m["hidden_size"] * m["vocab_size"] * new_tokens
    return float(2 * n * total + attn + head)
