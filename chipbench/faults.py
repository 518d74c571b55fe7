"""Faults planted under the timed path, to show that ``correct`` sees them.

Each is a context manager that breaks the program while it is built and
traced, and restores it on exit:

  unchanged    the step returns the state it was given (with the real
               step's metrics)
  half_batch   the loss keeps the first half of the batch's rows and takes
               its mean over them
  no_exchange  the allreduce strategy skips its collective: each chip
               keeps its own gradient

Neither the harness nor the program reads these; the calibration script
and the tests plant them.
"""
from __future__ import annotations

import contextlib

FAULTS = ("unchanged", "half_batch", "no_exchange")


@contextlib.contextmanager
def planted(name: str):
    import jax
    from repro.core import losses, strategies
    from chipbench import system

    saved = []

    def patch(obj, attr, value):
        saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, value)

    if name == "unchanged":
        build = system.build

        def broken_build(cell, devices):
            sysm = build(cell, devices)
            step = sysm.ts.step_fn
            sysm.ts.step_fn = jax.jit(lambda s, b: (s, step(s, b)[1]))
            return sysm
        patch(system, "build", broken_build)
    elif name == "half_batch":
        for attr in ("softmax_cross_entropy", "classification_loss"):
            fn = getattr(losses, attr)

            def half(logits, labels, fn=fn):
                keep = labels.shape[0] // 2
                return fn(logits[:keep], labels[:keep])
            patch(losses, attr, half)
    elif name == "no_exchange":
        patch(strategies.AllReduce, "sync",
              lambda self, grads, state, axis_names: (grads, state, {}))
    else:
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
    try:
        yield
    finally:
        for obj, attr, value in reversed(saved):
            setattr(obj, attr, value)
