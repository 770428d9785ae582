"""The two halves of a batched dispatch's way back to the host, as every
template's ``batch_predict`` records them on the batcher's per-dispatch
trace (obs/trace ambient spans, no-ops with tracing off).

Launch side, inside the caller's ``dispatch.enqueue``:
``dispatch.copy_start`` (:func:`start_copies`) asks the runtime to copy
each output of the program just launched to the host as soon as the
program defines it. Collect side (:func:`await_and_fetch`):
``dispatch.device_wait`` then ``dispatch.fetch``, which times what is
then left of those copies, not a round trip to the device per array.
``prepare``, ``enqueue`` and ``results`` wrap template-specific work and
are plain ``span(...)`` blocks there.
"""

from __future__ import annotations

import numpy as np

from predictionio_tpu.obs.trace import active_trace, span


def start_copies(arrays: tuple) -> tuple:
    """Start the device-to-host copy of every output of a program that
    was just launched, and hand the outputs back. The call returns at
    once on an output that is not ready yet; ``np.asarray`` later picks
    up the finished copy. An output that is not a device array (a
    stand-in model's NumPy arrays) passes through untouched."""
    with span("dispatch.copy_start"):
        for a in arrays:
            start = getattr(a, "copy_to_host_async", None)
            if start is not None:
                start()
    return arrays


def await_and_fetch(arrays: tuple) -> tuple:
    """Device arrays -> NumPy. On traced dispatches only, the wait for
    the device is split from collecting the copies that
    :func:`start_copies` started; untraced, the first ``np.asarray`` is
    the one sync, as it always was."""
    if active_trace() is not None:
        import jax

        with span("dispatch.device_wait"):
            jax.block_until_ready(arrays)
    with span("dispatch.fetch"):
        return tuple(np.asarray(a) for a in arrays)
