"""Backend-switched flash attention wrapper ([B,H,S,D] layout)."""
from __future__ import annotations

import functools
from typing import Optional

from repro.kernels.backend import get_backend, with_reference_grad
from repro.kernels.flash_attention.kernel import flash_attention as _pallas
from repro.kernels.flash_attention.ref import attention_ref


def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None, **kw):
    """Kernel forward; gradients through ``attention_ref``."""
    backend = kw.pop("backend", None) or get_backend()
    masking = dict(scale=scale, causal=causal, window=window, softcap=softcap)
    reference = functools.partial(attention_ref, **masking)
    if backend == "ref":
        return reference(q, k, v)
    kernel = functools.partial(_pallas, interpret=backend == "interpret",
                               **masking, **kw)
    return with_reference_grad(kernel, reference)(q, k, v)
