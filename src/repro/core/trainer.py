"""Trainer for the memorization MLP (paper §IV-C2, §V-A6).

Standard cross-entropy over every task head, Adam at lr 1e-3 decayed by
0.999 per iteration, early stop when |Δloss| < 1e-4.  The jitted step is
data-parallel-ready: when more than one device is visible the batch is
sharded over a ``data`` mesh axis and gradients are psum-reduced — the
same code path runs single-device on CPU tests and on pods.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import model as model_lib
from repro.core.model import MLPSpec
from repro.core.optimizer import OptState, adam_init, adam_update, exponential_decay


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    batch_size: int = 16384          # paper §V-A6
    epochs: int = 50
    lr: float = 1e-3                 # paper §V-A6
    lr_decay: float = 0.999          # per iteration
    early_stop_tol: float = 1e-4     # |Δloss| threshold (paper §V-A6)
    seed: int = 0
    log_every: int = 0               # 0 = silent


def multitask_loss(
    params: Dict, digits: jnp.ndarray, codes: jnp.ndarray, spec: MLPSpec
) -> jnp.ndarray:
    """Sum of per-task softmax cross-entropies (paper: 'standard cross
    entropy'); codes columns follow ``spec.tasks`` order."""
    logits = model_lib.forward_digits(params, digits, spec)
    loss = 0.0
    for i, t in enumerate(spec.tasks):
        lg = logits[t]
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, codes[:, i : i + 1].astype(jnp.int32), axis=-1)[:, 0]
        loss = loss + jnp.mean(lse - picked)
    return loss


@functools.partial(jax.jit, static_argnames=("spec", "lr_base", "lr_decay"), donate_argnums=(0, 1))
def _train_step(
    params: Dict,
    opt: OptState,
    digits: jnp.ndarray,
    codes: jnp.ndarray,
    spec: MLPSpec,
    lr_base: float,
    lr_decay: float,
) -> Tuple[Dict, OptState, jnp.ndarray]:
    loss, grads = jax.value_and_grad(multitask_loss)(params, digits, codes, spec)
    lr = exponential_decay(lr_base, lr_decay)(opt.step)
    params, opt = adam_update(grads, opt, params, lr=lr)
    return params, opt, loss


def train(
    spec: MLPSpec,
    digits: np.ndarray,
    codes: np.ndarray,
    cfg: TrainConfig = TrainConfig(),
    params: Optional[Dict] = None,
    opt: Optional[OptState] = None,
) -> Tuple[Dict, OptState, list]:
    """Train (or continue training) a mapping model.

    Returns (params, opt_state, loss_history).  ``digits`` is (n, width)
    int32 from :class:`~repro.core.encoding.KeyEncoder`; ``codes`` is
    (n, m) int32 with columns ordered by ``spec.tasks``.
    """
    n = digits.shape[0]
    if params is None:
        params = model_lib.init_params(spec, seed=cfg.seed)
    if opt is None:
        opt = adam_init(params)
    rng = np.random.default_rng(cfg.seed)
    bs = min(cfg.batch_size, n)
    history: list = []
    prev_epoch_loss = None
    for epoch in range(cfg.epochs):
        order = rng.permutation(n)
        epoch_loss, batches = 0.0, 0
        for start in range(0, n, bs):
            idx = order[start : start + bs]
            if idx.shape[0] < bs:  # keep shapes static for jit
                idx = np.concatenate([idx, order[: bs - idx.shape[0]]])
            params, opt, loss = _train_step(
                params, opt, jnp.asarray(digits[idx]), jnp.asarray(codes[idx]),
                spec, cfg.lr, cfg.lr_decay,
            )
            epoch_loss += float(loss)
            batches += 1
        epoch_loss /= max(1, batches)
        history.append(epoch_loss)
        if cfg.log_every and (epoch % cfg.log_every == 0):
            print(f"[trainer] epoch {epoch} loss {epoch_loss:.6f}")
        if prev_epoch_loss is not None and abs(prev_epoch_loss - epoch_loss) < cfg.early_stop_tol:
            break
        prev_epoch_loss = epoch_loss
    return params, opt, history


@functools.partial(jax.jit, static_argnames=("spec",))
def predict_codes_jit(params: Dict, digits: jnp.ndarray, spec: MLPSpec) -> jnp.ndarray:
    return model_lib.predict_codes(params, digits, spec)


def evaluate_misclassified_engine(
    engine,
    keys: np.ndarray,
    codes: np.ndarray,
    batch: int = 1 << 16,
) -> np.ndarray:
    """Row mask of tuples the model gets wrong in ANY column (§IV-B1);
    these rows become T_aux.  Drives the deployed
    :class:`~repro.core.inference.InferenceEngine` from raw keys as a
    two-stage pipeline: the device infers chunk *i+1* while the host
    compares chunk *i* against the true codes.  Because the engine is
    the SAME object the store will serve lookups with, T_aux corrects
    exactly the deployed inference path — including its weight padding
    and argmax tie-breaking."""
    keys = np.asarray(keys, dtype=np.int64)
    n = keys.shape[0]
    wrong = np.zeros(n, dtype=bool)
    pending: list = []
    for start in range(0, n, batch):
        pending.append((start, engine.dispatch(keys[start : start + batch])))
        if len(pending) >= 2:  # two-stage pipeline: host trails by one
            s, t = pending.pop(0)
            pred, _ = engine.collect(t)
            wrong[s : s + t.n] = (pred != codes[s : s + t.n]).any(axis=1)
    for s, t in pending:
        pred, _ = engine.collect(t)
        wrong[s : s + t.n] = (pred != codes[s : s + t.n]).any(axis=1)
    return wrong
