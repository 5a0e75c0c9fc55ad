"""Bytes that detector pass 1's kernel (``robust_hit_blocks``) needs.

"Least bytes" counts each input read once and each output written once
at its unpadded shape: padding the kernel adds is waste, not work.
"""


def least_bytes(S: int, B: int, T: int, n: int) -> int:
    """S seeds, B metrics, T ticks, n nodes: read the float32 metric
    block and the bool cohort mask, write the int32 vote counts."""
    return 4 * S * B * T * n + S * T * n + 4 * S * T * n
