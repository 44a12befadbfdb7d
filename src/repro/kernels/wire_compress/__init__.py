"""Fused wire-compressor pipeline (pallas): QSGD quantize+pack."""
from .ops import qsgd_pack
from .ref import qsgd_decode_ref, qsgd_quantize_pack_ref
from .wire_compress import LANE, pack_factor, qsgd_pack_pallas

__all__ = [
    "LANE",
    "pack_factor",
    "qsgd_pack",
    "qsgd_pack_pallas",
    "qsgd_decode_ref",
    "qsgd_quantize_pack_ref",
]
