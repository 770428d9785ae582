"""The tail of a batched dispatch, as every template's ``batch_predict``
records it on the batcher's per-dispatch trace (obs/trace ambient
spans, no-ops with tracing off): ``dispatch.device_wait`` then
``dispatch.fetch``. ``prepare``, ``enqueue`` and ``results`` wrap
template-specific work and are plain ``span(...)`` blocks there.
"""

from __future__ import annotations

import numpy as np

from predictionio_tpu.obs.trace import active_trace, span


def await_and_fetch(arrays: tuple) -> tuple:
    """Device arrays -> NumPy. On traced dispatches only, the wait for
    the device is split from the copy back; untraced, the first
    ``np.asarray`` is the one sync, as it always was."""
    if active_trace() is not None:
        import jax

        with span("dispatch.device_wait"):
            jax.block_until_ready(arrays)
    with span("dispatch.fetch"):
        return tuple(np.asarray(a) for a in arrays)
