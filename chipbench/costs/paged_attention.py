"""Work of one paged decode-attention call, as the algorithm needs it.

Counts the K/V pages that hold valid tokens (whole pages, since a page is
the unit read), the query and the output, and the block-table entries of
those pages; operations are the two matmuls, QK^T and PV, over the valid
tokens.  Pages past a sequence's length are not counted, whatever the
kernel's grid walks.
"""
NAMES = ("paged_attention",)


def work(geometry: dict, dims: dict, lens: list[int]) -> tuple[float, float]:
    """(operations, bytes) of one call over sequences of cache lengths
    ``lens``, for one layer."""
    page, hkv, hd = geometry["page"], geometry["Hkv"], geometry["hd"]
    b = geometry["bytes_per_elem"]
    H = dims["H"]
    pages = sum(-(-n // page) for n in lens)
    kv = pages * page * hkv * hd * 2 * b
    q_out = 2 * len(lens) * H * hd * b
    table = 4 * (pages + len(lens))
    ops = sum(4 * H * hd * n for n in lens)
    return float(ops), float(kv + q_out + table)
