"""Operations and bytes of the work the served path did, from shapes.

Counts are of useful work: real tokens and rows, not the padding of a
bucket; KV is read page by page by the decode kernel and token by token by
host attention.  ``dims`` is ``run.model_dims`` of the configuration.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

import numpy as np

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def prefill_work(prompt_len: int) -> Tuple[int, int]:
    """(tokens, causal query-key pairs) of one prefill."""
    return prompt_len, prompt_len * (prompt_len + 1) // 2


def layer_matmul_params(dims: Dict) -> int:
    d, H, KV, hd, f = (dims[k] for k in ("d", "H", "KV", "hd", "f"))
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f


def forward_flops(work: Dict, dims: Dict) -> float:
    """Forward FLOPs of the work counted by the benchmark's wraps: dense
    matmuls of every prompt and output token, the output head once per
    prefill (only the last position's logits are computed) and once per
    decode row, and attention over the live context of the prompts and of
    the rows decoded on the device (host rows attend on the CPU)."""
    L, d, V, H, hd = (dims[k] for k in ("L", "d", "V", "H", "hd"))
    tokens = work["prefill_tokens"] + work["decode_rows"]
    heads = work["prefill_requests"] + work["decode_rows"]
    dense = 2.0 * L * layer_matmul_params(dims) * tokens + 2.0 * d * V * heads
    attn = 4.0 * L * H * hd * (work["attn_pairs"] + work["device_ctx"])
    return dense + attn


def paged_decode_bytes(dev_lens: Iterable[int], page: int, dims: Dict) -> int:
    """Least bytes one fused decode step's paged-attention kernel calls move,
    over all layers: q and the output of each device row, and the live KV
    pages of each (``len`` tokens, the new one included)."""
    b = BYTES[dims["dtype"]]
    lens = np.asarray(list(dev_lens), np.int64)
    pages = int(np.sum(-(-lens // page)))
    qo = 2 * len(lens) * dims["H"] * dims["hd"] * b
    kv = 2 * pages * page * dims["KV"] * dims["hd"] * b
    return int(dims["L"] * (qo + kv))


def host_attn_bytes(lens: np.ndarray, kv_heads: int, head_dim: int,
                    itemsize: int) -> int:
    """KV bytes one ``HostAttention.run_layer`` call has to read: K and V of
    every cached token of each host row, the appended one included."""
    tokens = int(np.sum(np.asarray(lens, np.int64) + 1))
    return 2 * tokens * kv_heads * head_dim * itemsize
