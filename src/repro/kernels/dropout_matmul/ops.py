"""Backend-switched wrapper for the block-sparse dropout matmul."""
from __future__ import annotations

import functools

from repro.kernels.backend import get_backend, with_reference_grad
from repro.kernels.dropout_matmul.kernel import dropout_matmul as _pallas
from repro.kernels.dropout_matmul.ref import dropout_matmul_ref


def dropout_matmul(x, w, mask_blocks, *, block_n: int = 128, **kw):
    """y[g] = (x[g] @ w) * expand(mask[g]); dropped blocks are skipped on TPU.

    x: [G, M, K]; w: [K, N]; mask_blocks: [G, N / block_n] in {0, 1/keep}.
    Kernel forward; gradients through ``dropout_matmul_ref``.
    """
    backend = kw.pop("backend", None) or get_backend()
    reference = functools.partial(dropout_matmul_ref, block_n=block_n)
    if backend == "ref":
        return reference(x, w, mask_blocks)
    kernel = functools.partial(_pallas, block_n=block_n,
                               interpret=backend == "interpret", **kw)
    return with_reference_grad(kernel, reference)(x, w, mask_blocks)
