"""Jit'd dispatch layer over the Pallas kernels.

``use_pallas`` selects the kernel path.  Kernels compile for the TPU by
default; CPU tests must ask for the Pallas interpreter explicitly
(``interpret=True``), so a run on the chip never interprets by accident.
The default pure-JAX path is what the 512-device dry-run lowers (Pallas
TPU kernels cannot lower on a CPU-only host); on hardware the kernels are
drop-in via ``set_kernel_mode``.
"""
from __future__ import annotations

from contextlib import contextmanager


from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _pl_decode
from repro.kernels.flash_attention import flash_attention as _pl_flash
from repro.kernels.matmul import matmul as _pl_matmul
from repro.kernels.rmsnorm import rmsnorm as _pl_rmsnorm
from repro.kernels.ssd_scan import ssd_scan as _pl_ssd

_MODE = {"use_pallas": False, "interpret": False}


def set_kernel_mode(use_pallas: bool, interpret: bool = False):
    _MODE["use_pallas"] = use_pallas
    _MODE["interpret"] = interpret


@contextmanager
def kernel_mode(use_pallas: bool, interpret: bool = False):
    old = dict(_MODE)
    set_kernel_mode(use_pallas, interpret)
    try:
        yield
    finally:
        _MODE.update(old)


def matmul(a, b, **kw):
    if _MODE["use_pallas"]:
        return _pl_matmul(a, b, interpret=_MODE["interpret"], **kw)
    return ref.ref_matmul(a, b)


def rmsnorm(x, scale, **kw):
    if _MODE["use_pallas"]:
        return _pl_rmsnorm(x, scale, interpret=_MODE["interpret"], **kw)
    return ref.ref_rmsnorm(x, scale)


def flash_attention(q, k, v, *, causal=True, window=0, scale=None, **kw):
    if _MODE["use_pallas"]:
        return _pl_flash(q, k, v, causal=causal, window=window, scale=scale,
                         interpret=_MODE["interpret"], **kw)
    return ref.ref_flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale)


def decode_attention(q, k, v, length, *, scale=None, **kw):
    if _MODE["use_pallas"]:
        return _pl_decode(q, k, v, length, scale=scale,
                          interpret=_MODE["interpret"], **kw)
    return ref.ref_decode_attention(q, k, v, length, scale=scale)


def ssd_scan(x, dt, B, C, A, *, chunk=128, **kw):
    if _MODE["use_pallas"]:
        return _pl_ssd(x, dt, B, C, A, chunk=chunk,
                       interpret=_MODE["interpret"], **kw)
    y, _ = ref.ref_ssd_scan(x, dt, B, C, A)
    return y
