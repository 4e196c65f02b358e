"""Pallas TPU kernels for the BCPNN hot spots (+ pure-jnp oracles).

Dense kernels run on pad-to-aligned tiling plans (tiling.py); patchy
projections stream a compact gathered layout (patchy.py); block sizes
come from those plans unless the caller passes explicit ``block_*``
kwargs.
"""
from .ops import (bcpnn_fwd, bcpnn_update, fused_forward, fused_learn,
                  fused_packed_forward, hc_softmax)
from .patchy import (active_pre_hcs, compact_forward, compact_update,
                     patchy_forward, patchy_update)
from .quant import (dequantize_compact, dequantize_dense,
                    quant_compact_forward, quant_fwd_pallas,
                    quant_patchy_forward, quantize_acts, quantize_compact,
                    quantize_dense)
from .ref import ref_bcpnn_fwd, ref_bcpnn_update, ref_hc_softmax

__all__ = [
    "bcpnn_fwd", "bcpnn_update", "fused_forward", "fused_learn", "hc_softmax",
    "fused_packed_forward",
    "active_pre_hcs", "patchy_forward", "patchy_update",
    "compact_forward", "compact_update",
    "quantize_acts", "quantize_dense", "quantize_compact",
    "dequantize_dense", "dequantize_compact",
    "quant_fwd_pallas", "quant_patchy_forward", "quant_compact_forward",
    "ref_bcpnn_fwd", "ref_bcpnn_update", "ref_hc_softmax",
]
