"""Faults planted in the timed path, to show that the check catches them.

Each function breaks a runner's object (``runners.train.Train`` or
``runners.chain.Chain``) in place, underneath the harness: the window and
the check then run as in any run.  The self-tests plant them at a small
size on the CPU; ``bench/calibrate.py`` reads them on the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def unchanged(obj) -> None:
    """A step that returns its state unchanged."""
    if hasattr(obj, "step"):
        step = obj.step
        obj.step = lambda p, x, y: (p, step(p, x, y)[1])
    else:
        obj.product = lambda c: c


def half_batch(obj) -> None:
    """Half of the batch left out, the mean taken over the rest: the loss
    over the first half of the nodes, or a product over the first half of
    C's rows."""
    if hasattr(obj, "step"):
        from repro.launch.steps import make_gcn_train_step
        model = obj.model

        def loss(params, x, labels, **kw):
            logp = jax.nn.log_softmax(model.forward(params, x, **kw), -1)
            half = labels.shape[0] // 2
            return -jnp.mean(jnp.take_along_axis(
                logp[:half], labels[:half, None], 1))
        model.loss = loss
        obj.step = make_gcn_train_step(model, lr=obj.lr, backend="auto")
    else:
        product = obj.product

        def halved(c):
            half = c.shape[0] // 2
            return product(c.at[half:].set(0.0))
        obj.product = halved


def altered(obj) -> None:
    """One answer altered where it is produced: one entry of the
    parameters a training step returns, or of each product."""
    if hasattr(obj, "step"):
        step = obj.step

        def bumped(p, x, y):
            p, loss = step(p, x, y)
            return [p[0].at[0, 0].add(1.0)] + list(p[1:]), loss
        obj.step = bumped
    else:
        product = obj.product
        obj.product = lambda c: product(c).at[0, 0].add(1.0)


def control(obj) -> None:
    """Not a fault: the chain's control, the reference at the precision
    below put in the program's place, which the check must refuse as
    well."""
    from bench import reference
    a = reference.Diagonals(obj.graph)
    obj.product = lambda c: reference.chain_product(a, c, "high")


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "altered": altered}


def snapshot(obj) -> list:
    """What the faults change: the attributes of ``obj`` and of its model."""
    return [(o, dict(vars(o))) for o in (obj, getattr(obj, "model", None))
            if o is not None]


def restore(obj, saved: list) -> None:
    for o, attrs in saved:
        o.__dict__.clear()
        o.__dict__.update(attrs)
