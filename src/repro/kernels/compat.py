"""Where a Pallas kernel runs: compiled by Mosaic on a TPU, interpreted
elsewhere.

Every kernel wrapper resolves its ``interpret`` argument here.  Off the TPU
the Pallas interpreter runs the kernel body, bit-faithfully, which is how the
CPU tests exercise every kernel (flash_attn, paged_attn, bitplane_mac,
imc_mac, rbl_decode).  On a TPU every kernel compiles: an interpreted kernel
there would be a slow stand-in that hides what the device does.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` interprets exactly off the TPU; an explicit bool wins, except
    that a TPU never interprets (raises ``ValueError``)."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas kernels compile on a TPU; interpret=True is "
                         "for backends Mosaic does not target")
    return interpret
