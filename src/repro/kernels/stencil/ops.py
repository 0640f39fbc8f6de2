"""Jit'd public op + KERNELS registry for the futurized runtime
(``device.create_program_with_file(".../stencil/ops.py")``)."""
from __future__ import annotations

import jax

from repro.kernels.stencil.kernel import LANES, sublanes
from repro.kernels.stencil.kernel import stencil as _pallas_stencil
from repro.kernels.stencil.ref import stencil_ref


def stencil(x, *, block=None, grid=None, impl: str = "auto"):
    """3-point stencil: the Pallas kernel, or the oracle with
    ``impl="ref"``.  ``block`` may come from the launch geometry
    (Dim3 -> tuple) of ``Program.run``; it defaults to one
    ``(sublanes, 128)`` tile.  A shape the kernel cannot tile raises
    ``ValueError``; there is no silent fallback to the oracle."""
    if impl == "ref":
        return stencil_ref(x)
    blk = (block[0] if isinstance(block, (tuple, list)) else block) or (
        sublanes(x.dtype) * LANES)
    return _pallas_stencil(x, block=blk, interpret=jax.default_backend() != "tpu")


KERNELS = {"stencil": stencil, "stencil_ref": stencil_ref}
