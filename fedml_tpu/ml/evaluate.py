"""Sharded model evaluation.

Replaces the reference's ``_local_test_on_all_clients``
(``simulation/sp/fedavg/fedavg_api.py:174-232``) central torch eval loops with
one jit'd batched pass; metric definitions preserved (accuracy = correct/total,
NWP accuracy ignores pad tokens, tagpred reports mean F1) so the §6 baseline
numbers are comparable.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..core.mlops.scopes import evaluate_scope
from .losses import get_loss_fn


def make_eval_fn(bundle, batch_size: int = 256):
    loss_fn_raw = get_loss_fn(bundle.task)

    @partial(jax.jit, static_argnums=())
    def eval_batch(params, bx, by, bmask):
        with evaluate_scope("evaluate"):
            logits = bundle.apply(params, bx, train=False)
            loss, metrics = loss_fn_raw(logits, by, bmask)
        return (
            (metrics["loss_sum"]).sum(),
            metrics["correct"],
            metrics["count"],
        )

    def evaluate(params, test_x, test_y) -> Dict[str, float]:
        n = test_x.shape[0]
        pad = (-n) % batch_size
        if pad:
            test_x = np.concatenate([test_x, np.zeros((pad,) + test_x.shape[1:], test_x.dtype)])
            test_y = np.concatenate([test_y, np.zeros((pad,) + test_y.shape[1:], test_y.dtype)])
        mask_full = (np.arange(test_x.shape[0]) < n).astype(np.float32)
        tot_loss = tot_correct = tot_count = 0.0
        for i in range(0, test_x.shape[0], batch_size):
            ls, c, cnt = eval_batch(
                params,
                jnp.asarray(test_x[i : i + batch_size]),
                jnp.asarray(test_y[i : i + batch_size]),
                jnp.asarray(mask_full[i : i + batch_size]),
            )
            tot_loss += float(ls)
            tot_correct += float(c)
            tot_count += float(cnt)
        return {
            "test_loss": tot_loss / max(tot_count, 1.0),
            "test_acc": tot_correct / max(tot_count, 1.0),
            "test_total": tot_count,
        }

    return evaluate
