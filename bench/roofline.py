"""Peaks of the chip and the work of one collapsed-Gibbs token update.

The work is that of the plain dense update, whatever implements it: per
token, read the document's row of ``D`` (K int32) and the word's row of
``Ŵ`` (K float32), 8·K bytes, and make about 4·K operations (add α,
multiply, prefix-sum, compare). It depends on K alone, so a share of it
stays comparable when a later path does less work per token.
"""

from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       f"{PEAKS_FILE.name} with its source")
    return table[device_kind]


def token_work(n_topics: int) -> tuple[float, float]:
    """(operations, bytes) of one token's dense collapsed-Gibbs update."""
    return 4.0 * n_topics, 8.0 * n_topics


def roofline_tokens_per_s(n_topics: int, peak: dict) -> float:
    """The most tokens per second one chip could update: the work over
    whichever of its two peaks binds."""
    ops, nbytes = token_work(n_topics)
    return 1.0 / max(ops / peak["bf16_flops_per_s"],
                     nbytes / peak["hbm_bytes_per_s"])
