"""Batched serving driver: prefill + decode loop with KV cache, plus the
request-batching front end (``SubgraphFrontEnd``) of the tile-fusion
serving tier.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \\
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..core.sparse.formats import csr_content_digest
from ..core.tilefusion.serving import ServingTier
from ..models import transformer as T
from . import steps


class SubgraphFrontEnd:
    """Request-batching front of a ``ServingTier`` for GNN-style loads.

    Each request is ``(a, feats, w)`` — a sampled subgraph, its node
    features ``(a.n_cols, feat_dim)``, and a per-request weight
    ``(feat_dim, out_dim)`` — computing ``a @ (feats @ w)``.  ``submit``
    queues; ``flush`` groups queued requests by served pattern and stacks
    up to ``max_batch`` of them into ONE tier dispatch: features go
    side-by-side in B's columns and the weights block-diagonally in C, so
    one schedule lookup and one executor launch serve the whole stack
    (unused column blocks stay zero — the compiled shape never changes).
    Results come back in submit order."""

    def __init__(self, feat_dim: int, out_dim: int, max_batch: int = 4,
                 **tier_kw):
        self.feat_dim = feat_dim
        self.out_dim = out_dim
        self.max_batch = max(int(max_batch), 1)
        self.tier = ServingTier(b_col=feat_dim * self.max_batch,
                                c_col=out_dim * self.max_batch, **tier_kw)
        self._queue: list = []
        self.batches = 0

    def submit(self, a, feats, w) -> int:
        """Queue a request; returns its index into ``flush()``'s result."""
        self._queue.append((a, np.asarray(feats), np.asarray(w)))
        return len(self._queue) - 1

    def flush(self) -> list:
        """Serve every queued request; list of ``(n_rows, out_dim)`` outputs
        in submit order."""
        queue, self._queue = self._queue, []
        results: list = [None] * len(queue)
        groups: dict = {}
        for i, (a, _, _) in enumerate(queue):
            groups.setdefault(csr_content_digest(a), []).append(i)
        fd, od = self.feat_dim, self.out_dim
        for idxs in groups.values():
            for lo in range(0, len(idxs), self.max_batch):
                chunk = idxs[lo: lo + self.max_batch]
                a = queue[chunk[0]][0]
                b = np.zeros((a.n_cols, fd * self.max_batch), np.float32)
                c = np.zeros((fd * self.max_batch, od * self.max_batch),
                             np.float32)
                for s, i in enumerate(chunk):
                    b[:, s * fd:(s + 1) * fd] = queue[i][1]
                    c[s * fd:(s + 1) * fd, s * od:(s + 1) * od] = queue[i][2]
                d = np.asarray(self.tier.matmul(a, b, c))
                # the stacked call resolved the schedule once; count the
                # piggy-backed requests so tier stats stay per-request
                for _ in chunk[1:]:
                    self.tier.schedule_for(a)
                for s, i in enumerate(chunk):
                    results[i] = d[:, s * od:(s + 1) * od]
                self.batches += 1
        return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    key = jax.random.PRNGKey(args.seed)
    params = T.init_params(cfg, key)
    max_len = args.prompt_len + args.gen

    b = args.batch
    prompts = jax.random.randint(key, (b, args.prompt_len), 0, cfg.vocab_size)
    serve_step = steps.make_serve_step(cfg, rules=None, jit=True)

    # batched prefill: one compiled call fills every layer's KV/state cache
    cache = T.init_cache(cfg, b, max_len)
    t0 = time.time()
    batch = {"tokens": prompts}
    if cfg.encoder_layers:
        batch["enc_embeds"] = jnp.zeros(
            (b, cfg.encoder_seq, cfg.d_model), jnp.float32)
    next_tok, cache = serve_step(params, batch, cache, jnp.int32(0))
    prefill_t = time.time() - t0

    out = [next_tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        batch = {"tokens": next_tok[:, None]}
        if cfg.encoder_layers:
            batch["enc_embeds"] = jnp.zeros(
                (b, cfg.encoder_seq, cfg.d_model), jnp.float32)
        next_tok, cache = serve_step(
            params, batch, cache, jnp.int32(args.prompt_len + i))
        out.append(next_tok)
    gen_t = time.time() - t0
    tokens = jnp.stack(out, axis=1)
    print(f"generated {tokens.shape} in {gen_t:.2f}s "
          f"({b * (args.gen - 1) / max(gen_t, 1e-9):.1f} tok/s), "
          f"prefill {prefill_t:.2f}s")
    print("sample:", tokens[0, :16].tolist())


if __name__ == "__main__":
    main()
