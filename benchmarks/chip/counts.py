"""Operations and bytes a served step needs, and the roofline they set.

Each architecture module (``archs/<architecture>.py``) counts its own
steps from the model's shapes (``prefill_flops``, ``decode_flops``,
``decode_bytes``, ``weight_bytes``, ``kv_bytes_per_token``), by one rule:
counts are of what the algorithm needs, not of what the program happens
to compute: causal attention over the valid context only, the output head
on the rows whose logits are used, the key/value cache read over each
slot's valid context.  A program that does more (reads the whole padded
cache, projects every prompt row onto the vocabulary) shows as a lower
share of its roofline.  ``cfg`` is a ``repro.configs.base.ModelConfig``;
``w`` is the weight size in bytes (2 for bfloat16).
"""
from __future__ import annotations


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
