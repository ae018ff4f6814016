"""repro.kernels — Pallas TPU kernels for the compute hot-spots.

Each kernel ships three files per the repo convention:
``kernel.py`` (pl.pallas_call + BlockSpec VMEM tiling), ``ops.py``
(jit'd public wrapper: padding/layout/GQA broadcast) and ``ref.py``
(pure-jnp oracle the tests sweep against).

* ``stencil``         — fused 5-point Jacobi sweep: the paper's flagship
                         app (§6) with its §7 ufunc-merging implemented
                         at the VMEM level (1 read + 1 write per sweep).
* ``flash_attention`` — causal/GQA/SWA online-softmax attention.
* ``mamba2_scan``     — chunked SSD scan (zamba2's mixer).
* ``rwkv6_wkv``       — chunked data-dependent-decay wkv recurrence.

Every wrapper takes ``interpret=None``, resolved by
:func:`resolve_interpret`: the Pallas interpreter runs on the CPU
platform only, so a kernel called on an accelerator is always compiled.
"""
from typing import Optional


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` means: interpret on the CPU platform, compile elsewhere.
    An explicit bool is kept (compile-only tests pass ``False`` on CPU)."""
    if interpret is not None:
        return interpret
    import jax

    return jax.default_backend() == "cpu"
