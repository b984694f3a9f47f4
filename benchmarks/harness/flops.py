"""What the attention products of a step require, shared by the families'
``flops/<family>.py``: 4*B*H*Sq*Sk*D forward, 8 backward; a causal site
needs half."""

from __future__ import annotations


def attention_flops(sites: list[dict]) -> float:
    """Forward + backward (4 + 8) of every site of ``attention_sites``."""
    total = 0.0
    for s in sites:
        one = 12.0 * s["b"] * s["h"] * s["sq"] * s["sk"] * s["d"]
        total += s["count"] * (one / 2 if s["causal"] else one)
    return total
