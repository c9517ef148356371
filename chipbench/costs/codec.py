"""Work of the fused transit codec for one page of one layer's K or V.

Page-out (gather_quantize_crc) reads the page and writes its int8 rows,
one float32 scale per row and one Adler-32 word.  Page-in
(scatter_dequantize_crc) reads those and writes the page.  Operations
are elementwise (absmax, divide, round; two multiply-adds of the
checksum), counted at 8 per element.
"""
NAMES = ("gather_quantize_crc", "scatter_dequantize_crc")
OPS_PER_ELEM = 8


def _page(geometry: dict) -> tuple[int, int, int]:
    elems = geometry["page"] * geometry["Hkv"] * geometry["hd"]
    packed = elems + 4 * geometry["page"] + 4
    return elems, packed, geometry["bytes_per_elem"]


def page_out(geometry: dict) -> tuple[float, float]:
    elems, packed, b = _page(geometry)
    return float(OPS_PER_ELEM * elems), float(elems * b + packed)


def page_in(geometry: dict) -> tuple[float, float]:
    elems, packed, b = _page(geometry)
    return float(OPS_PER_ELEM * elems), float(packed + elems * b)
