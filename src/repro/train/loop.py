"""Host-side training loop with logging and checkpointing."""
from __future__ import annotations

import time
from typing import Callable, Iterable

import jax
import numpy as np


def train(train_step, state, batches: Iterable, n_steps: int,
          log_every: int = 10, checkpoint_fn: Callable | None = None,
          checkpoint_every: int = 0, log_fn=print):
    """Run the compiled train step over a batch iterator."""
    # the state is donated: old and new params/optimizer state never
    # have to fit on the device together
    step_fn = (train_step if hasattr(train_step, "lower")
               else jax.jit(train_step, donate_argnums=0))
    history = []
    t0 = time.time()
    tokens_seen = 0
    for i, batch in enumerate(batches):
        if i >= n_steps:
            break
        state, metrics = step_fn(state, batch)
        tok = int(np.prod(np.asarray(batch["tokens"]).shape))
        tokens_seen += tok
        if (i + 1) % log_every == 0 or i == 0:
            m = {k: float(v) for k, v in metrics.items()}
            dt = time.time() - t0
            m.update(step=i + 1, wall_s=round(dt, 2),
                     tok_per_s=round(tokens_seen / max(dt, 1e-9)))
            history.append(m)
            log_fn(f"step {i+1:5d}  loss {m['loss']:.4f}  "
                   f"tok/s {m['tok_per_s']:.0f}  wall {m['wall_s']:.1f}s")
        if checkpoint_fn and checkpoint_every and (i + 1) % checkpoint_every == 0:
            checkpoint_fn(state, i + 1)
    return state, history
