"""The plain reference the Trainer's first window is held to: the same
``loss_fn`` and optimizer in a bare ``jit(value_and_grad)`` loop, one
call per step, fed by ``jax.device_put`` of the host-regenerated window.
No scan, no loader, no ``Trainer``."""

from __future__ import annotations

import time
from typing import Any, List, Sequence, Tuple


def first_window_losses(loss_fn, optimizer, params: Any,
                        batches: Sequence[Any],
                        replicated: Any) -> Tuple[List[float], float]:
    """Run one optimizer step per batch from ``params`` (donated: the
    caller keeps its own copy), everything but the batch under the
    ``replicated`` sharding.  Returns (loss per step, seconds of the last
    step with the device waited for)."""
    import functools

    import jax
    import optax

    # Outputs pinned to the inputs' sharding: the second call then looks
    # like the first and the step compiles once.
    @functools.partial(
        jax.jit, donate_argnums=(0, 1), out_shardings=replicated
    )
    def step(p, o, b):
        loss, grads = jax.value_and_grad(loss_fn)(p, b)
        updates, o = optimizer.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    opt_state = jax.jit(optimizer.init, out_shardings=replicated)(params)
    losses, last_s = [], 0.0
    for b in batches:
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, b)
        losses.append(float(loss))
        last_s = time.perf_counter() - t0
    del params, opt_state
    return losses, last_s
