"""Post-pruning evaluation: perplexity + zero-shot-style accuracy proxy.

The paper evaluates WikiText perplexity and EleutherAI zero-shot accuracy.
Offline stand-ins (DESIGN §9): perplexity on the synthetic validation
split, and a zero-shot proxy = next-token top-1 accuracy on held-out
sequences (a task the model was never tuned for; rank-based like the
multiple-choice harness tasks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.data import synthetic
from repro.models import ModelApi
from repro.train import steps as steps_lib


def val_batches(cfg_arch, *, n_batches: int = 4, batch: int = 8,
                seq: int = 128, seed: int = 0):
    corpus = synthetic.CorpusConfig(cfg_arch.vocab_size, seed=seed)
    pipe = synthetic.DataPipeline(corpus, batch, seq, split="val")
    key = jax.random.key(seed + 1)
    return [synthetic.with_modality(pipe.get(i), cfg_arch,
                                    jax.random.fold_in(key, i))
            for i in range(n_batches)]


def perplexity(api: ModelApi, params, batches, *, masks=None) -> float:
    return steps_lib.perplexity(api, params, batches, masks=masks)


def make_acc_step(api: ModelApi, *, masks=None):
    """jit'd (params, batch) -> (top-1 hits, valid tokens); ``masks`` go
    in as an argument, not a closure constant (``steps.make_eval_step``)."""
    @jax.jit
    def step(params, batch, masks):
        hidden, _, _ = api.forward(params, batch, masks=masks)
        logits = api.module.lm_head(params, hidden, api.cfg)
        pred = jnp.argmax(logits, axis=-1)
        valid = batch["labels"] >= 0
        hit = (pred == batch["labels"]) & valid
        return jnp.sum(hit), jnp.sum(valid)

    return functools.partial(step, masks=masks)


def top1_accuracy(api: ModelApi, params, batches, *, masks=None) -> float:
    """Zero-shot proxy: next-token top-1 accuracy (higher is better)."""
    step = make_acc_step(api, masks=masks)
    hits, total = 0.0, 0.0
    for b in batches:
        h, t = step(params, b)
        hits += float(h)
        total += float(t)
    return hits / max(total, 1.0)


def evaluate(api: ModelApi, params, *, masks=None, n_batches: int = 4,
             batch: int = 8, seq: int = 128, seed: int = 0) -> dict:
    bs = val_batches(api.cfg, n_batches=n_batches, batch=batch, seq=seq,
                     seed=seed)
    return {
        "perplexity": perplexity(api, params, bs, masks=masks),
        "accuracy": top1_accuracy(api, params, bs, masks=masks),
    }
