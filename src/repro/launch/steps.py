"""Step functions: train_step / prefill_step / serve_step factories."""
from __future__ import annotations


import jax
import jax.numpy as jnp

from ..models import transformer as T
from ..optim import adamw
from ..trace import scope


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return nll.mean()


def make_loss_fn(cfg, rules):
    def loss_fn(params, batch):
        logits = T.forward(cfg, params, batch, rules=rules)
        loss = cross_entropy(logits, batch["labels"])
        return loss, {"loss": loss}
    return loss_fn


def make_train_step(cfg, opt_cfg, rules, *, jit: bool = False):
    """Train-step factory.  ``jit=True`` returns the compiled step (the
    ``make_gcn_train_step`` convention) so drivers stop hand-wrapping;
    the default stays eager because the dry-run re-wraps with explicit
    in_shardings."""
    loss_fn = make_loss_fn(cfg, rules)

    def train_step(params, opt_state, batch):
        (loss, aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        params, opt_state, om = adamw.update(opt_cfg, grads, opt_state, params)
        return params, opt_state, {**aux, **om}
    return jax.jit(train_step) if jit else train_step


def make_gcn_train_step(model, *, lr: float = 0.3, backend: str = "auto",
                        mesh=None, jit: bool = True):
    """SGD train step for a ``models.gcn.GCN`` on the fused path.

    The returned ``step(params, x, y) -> (params, loss)`` differentiates
    through ``tile_fused_matmul``'s custom_vjp, so each layer's backward
    runs one transposed SpMM off the full-matrix hybrid-ELL cache and two
    dense GEMMs, whatever backend ``backend=`` (or Eq-3 auto selection)
    resolves to, including under a non-trivial ``mesh=``.
    ``jit=False`` returns the eager step (useful for cache-behavior
    tests)."""
    def step(params, x, y):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, x, y, backend=backend,
                                 mesh=mesh))(params)
        with scope("sgd.update"):
            return [w - lr * g for w, g in zip(params, grads)], loss
    return jax.jit(step) if jit else step


def make_prefill_step(cfg, rules, *, jit: bool = False):
    def prefill_step(params, batch):
        return T.forward(cfg, params, batch, rules=rules)
    return jax.jit(prefill_step) if jit else prefill_step


def make_serve_step(cfg, rules, *, jit: bool = False):
    """One decode step: new token in, next-token logits + updated cache out."""
    def serve_step(params, batch, cache, cache_len):
        logits, new_cache = T.decode_step(
            cfg, params, batch, cache, cache_len, rules=rules)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, new_cache
    return jax.jit(serve_step) if jit else serve_step
