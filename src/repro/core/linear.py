"""Model-facing linear op.

Every dense layer in the model zoo goes through :func:`linear`, which is
where the paper's technique integrates with the framework:

* weight arrives pre-packed (serving path — packed once at load by
  ``serve.engine.load_for_serving``): the call routes to the fused
  skinny-A Pallas kernel;
* weight is a plain array, the matmul is TSMM-shaped (prefill
  projections onto a skinny output — tall activations x narrow weight),
  AND the call traces inside :func:`serving_ctx` (the engine enters it
  around prefill/decode execution): the call routes through
  ``tsmm_dot``'s planned tall-A path, whose epilogue FUSES
  bias+activation into the kernel's final k step (DESIGN.md §11) —
  ``act(A@B + bias)`` executes in one kernel instead of paying a
  separate (m, n) round trip over HBM;
* everything else (training path, regular shapes) is a plain XLA GEMM.

The serving gate matters: the planned Pallas kernels carry no
differentiation rule, so routing a *training* matmul through them would
break ``jax.grad`` over the loss — inference-only fusion, by
construction.  Model code stays oblivious either way.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.packing import is_packed
from repro.core.plan import is_tsmm
from repro.core.tsmm import tsmm_dot
from repro.kernels.ref import act_ref

_SERVING = threading.local()


@contextlib.contextmanager
def serving_ctx():
    """Mark the enclosed (trace of a) model call as inference: TSMM-shaped
    unpacked matmuls may route through the planned fused path.  Entered
    by the serving engine around program execution — jit specializes at
    trace time, so the routing decision is baked into the compiled
    prefill/decode programs and never into training steps."""
    prev = getattr(_SERVING, "on", False)
    _SERVING.on = True
    try:
        yield
    finally:
        _SERVING.on = prev


def in_serving_ctx() -> bool:
    return getattr(_SERVING, "on", False)


def linear(x, w, b=None, act: Optional[str] = None, *,
           name: Optional[str] = None):
    """act(x @ w + b).  ``w``: (k, n) array or PackedTensor.

    ``name`` is the weight's leaf (``wq``, ``w_down``, ...): the call's
    ops are traced under ``jax.named_scope(name)`` and a planned kernel
    is named ``tsmm_<name>``, so a profile attributes device time to the
    leaf."""
    kernel = f"tsmm_{name}" if name else None
    with jax.named_scope(name) if name else contextlib.nullcontext():
        if is_packed(w) or (in_serving_ctx() and w.ndim == 2 and is_tsmm(
                math.prod(x.shape[:-1]), *w.shape)):
            return tsmm_dot(x, w, bias=b, act=act, name=kernel)
        out = jnp.dot(x, w)
        if b is not None:
            out = out + b.astype(out.dtype)
        if act is not None:
            out = act_ref(out.astype(jnp.float32), act).astype(x.dtype)
        return out
