"""Public jit'd wrappers for the LM path's Pallas kernels: flash
attention, the SSD scan and the RG-LRU scan.

Dispatch policy: on TPU backends the kernels run compiled (Mosaic); on any
other backend they run with ``interpret=True`` — the kernel body executes
in Python per grid cell, which is what the correctness sweeps in
tests/test_kernels.py rely on. Model code selects these via
``ModelConfig.attention_impl = 'pallas'``; the dry-run keeps the XLA
reference path because Pallas does not lower to CPU HLO.
"""
from __future__ import annotations

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import rglru_scan as _rg
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention_bshd(q, k, v, *, causal=True, window=0, q_offset=0):
    """(B,S,H,d) layout wrapper matching `models.blocks.chunked_attention`."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                              q_offset=q_offset, interpret=_interpret())
    return out.transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    block_q=128, block_kv=128):
    """(B,H,S,d) layout."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, block_q=block_q,
                               block_kv=block_kv, interpret=_interpret())


def ssd_scan(x, dt, A, Bm, Cm, *, chunk=128):
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                         interpret=_interpret())


def rglru_scan(a, b, *, chunk=128, block_w=128):
    return _rg.rglru_scan(a, b, chunk=chunk, block_w=block_w,
                          interpret=_interpret())
