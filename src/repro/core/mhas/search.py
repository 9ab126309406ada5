"""MHAS Algorithm 2: alternating shared-weight training and controller
REINFORCE updates, minimizing the paper's Eq. 1 over the hybrid.

The reward for a sampled child is the (estimated) hybrid compression
ratio: sliced-model bytes + estimated T_aux bytes (from the child's
row-level error rate on a held-out sample, scaled by a calibrated
compression factor) + V_exist + f_decode, over raw data bytes.
``run_mhas`` returns the best child re-sliced from the bank and
fine-tuned — the paper's "model search process is followed by training
to finetune the accuracy".
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trainer as trainer_lib
from repro.core.aux_table import AuxTable
from repro.core.encoding import KeyEncoder, build_codecs, onehot_digits
from repro.core.mhas import controller as ctrl_lib
from repro.core.mhas.search_space import SearchSpace
from repro.core.model import MLPSpec
from repro.core.optimizer import adam_init, adam_update
from repro.core.table import Table


@dataclasses.dataclass(frozen=True)
class MHASConfig:
    """Paper §V-A6 hyper-parameters (defaults scaled for CPU runs; the
    paper-scale values are in comments)."""

    layer_sizes: Tuple[int, ...] = (100, 200, 400, 800, 1200, 1600, 2000)
    max_layers: int = 2
    total_iters: int = 200            # N_t (paper: 2000)
    model_iters: int = 200            # N_m (paper: 2000)
    controller_iters: int = 4         # N_c (paper: 40 — 1 epoch / 50 iters)
    model_epochs_per_iter: int = 5    # paper: 5
    model_batch: int = 16384          # paper: 16384
    controller_batch: int = 2048      # paper: 2048 (reward eval batch)
    controller_samples: int = 8       # archs per controller update
    lr_model: float = 1e-3            # paper: 1e-3 (decay handled by Adam)
    lr_controller: float = 3.5e-4     # paper: 0.00035
    entropy_coef: float = 1e-3
    baseline_decay: float = 0.95
    early_stop_tol: float = 1e-4      # paper: |Δloss| < 0.0001
    finetune_epochs: int = 30
    seed: int = 0
    base: int = 10
    verbose: bool = False


@dataclasses.dataclass
class MHASResult:
    spec: MLPSpec
    params: Dict
    best_arch: Dict
    best_ratio: float
    history: List[Dict]              # per-sample: iter, ratio, child_params
    space: SearchSpace


# --------------------------------------------------------------------------
# jitted child train / eval on the shared bank
# --------------------------------------------------------------------------


def _child_loss(bank, onehot_pad, codes, aa, space: SearchSpace):
    logits = space.forward(bank, onehot_pad, aa)
    loss = 0.0
    for i, t in enumerate(space.tasks):
        lg = logits[t]
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, codes[:, i : i + 1].astype(jnp.int32), axis=-1)[:, 0]
        loss = loss + jnp.mean(lse - picked)
    return loss


@functools.partial(jax.jit, static_argnames=("space", "lr"), donate_argnums=(0, 1))
def _bank_step(bank, opt, onehot_pad, codes, aa, space: SearchSpace, lr: float):
    loss, grads = jax.value_and_grad(_child_loss)(bank, onehot_pad, codes, aa, space)
    bank, opt = adam_update(grads, opt, bank, lr=lr)
    return bank, opt, loss


@functools.partial(jax.jit, static_argnames=("space",))
def _child_errors(bank, onehot_pad, codes, aa, space: SearchSpace):
    logits = space.forward(bank, onehot_pad, aa)
    wrong = jnp.zeros(onehot_pad.shape[0], dtype=bool)
    for i, t in enumerate(space.tasks):
        pred = jnp.argmax(logits[t], axis=-1).astype(jnp.int32)
        wrong = wrong | (pred != codes[:, i])
    return wrong.mean()


# --------------------------------------------------------------------------
# the search driver
# --------------------------------------------------------------------------


class _RewardModel:
    """Eq. 1 estimate for a sampled child architecture."""

    def __init__(self, space: SearchSpace, table: Table, codes: np.ndarray, cfg: MHASConfig):
        self.space = space
        self.raw_bytes = table.raw_size_bytes()
        self.n = table.num_rows
        self.row_bytes = 8 + 4 * len(space.tasks)
        # Constant terms: V_exist (compressed) + f_decode.
        from repro.core.bitvector import BitVector

        self.const_bytes = BitVector.from_keys(table.keys).size_bytes()
        codecs = build_codecs(table.columns)
        self.const_bytes += sum(c.size_bytes() for c in codecs.values())
        # Calibrate the aux compression factor on a random row sample.
        rng = np.random.default_rng(cfg.seed)
        m = min(4096, self.n)
        idx = rng.choice(self.n, size=m, replace=False)
        aux = AuxTable.build(table.keys[idx], codes[idx], codec="zstd")
        self.aux_factor = aux.size_bytes() / max(1, m * self.row_bytes)

    def ratio(self, arch: Dict, err_rate: float) -> float:
        model_bytes = self.space.child_num_params(arch) * 4
        aux_bytes = err_rate * self.n * self.row_bytes * self.aux_factor
        return (model_bytes + aux_bytes + self.const_bytes) / max(1, self.raw_bytes)


def run_mhas(
    table: Table,
    cfg: MHASConfig = MHASConfig(),
    pool=None,
) -> MHASResult:
    """Search a hybrid architecture for ``table`` (Algorithm 2)."""
    encoder = KeyEncoder(table.max_key, base=cfg.base)
    codecs = build_codecs(table.columns)
    tasks = tuple(sorted(table.columns))
    space = SearchSpace(
        base=cfg.base,
        width=encoder.width,
        tasks=tasks,
        out_cards=tuple(codecs[t].cardinality for t in tasks),
        layer_sizes=cfg.layer_sizes,
        max_layers=cfg.max_layers,
    )
    digits = encoder.digits(table.keys)
    codes = np.stack([codecs[t].codes for t in tasks], axis=1)
    n = table.num_rows

    def onehot_pad(idx: np.ndarray) -> jnp.ndarray:
        oh = onehot_digits(jnp.asarray(digits[idx]), space.base)
        pad = space.max_width - oh.shape[-1]
        return jnp.pad(oh, ((0, 0), (0, pad)))

    bank = space.init_bank(seed=cfg.seed)
    bank_opt = adam_init(bank)
    cspec = ctrl_lib.ControllerSpec.for_space(space)
    cparams = ctrl_lib.init_controller(cspec, seed=cfg.seed)
    copt = adam_init(cparams)
    reward_model = _RewardModel(space, table, codes, cfg)

    rng = np.random.default_rng(cfg.seed)
    jrng = jax.random.PRNGKey(cfg.seed + 1)
    baseline = None
    best = {"ratio": float("inf"), "arch": None}
    history: List[Dict] = []
    bs = min(cfg.model_batch, n)
    rbs = min(cfg.controller_batch, n)

    model_every = max(1, cfg.total_iters // max(1, cfg.model_iters))
    ctrl_every = max(1, cfg.total_iters // max(1, cfg.controller_iters))
    prev_loss = None

    @jax.jit
    def ctrl_update(cparams, copt, tokens_batch, advantages):
        def loss_fn(cp):
            total = 0.0
            for tokens, adv in zip(tokens_batch, advantages):
                logp, ent = ctrl_lib.logprob_of(cp, cspec, tokens)
                total = total - adv * logp - cfg.entropy_coef * ent
            return total / len(tokens_batch)

        loss, grads = jax.value_and_grad(loss_fn)(cparams)
        cparams, copt = adam_update(grads, copt, cparams, lr=cfg.lr_controller)
        return cparams, copt, loss

    def sample_and_score(jrng):
        jrng, sub = jax.random.split(jrng)
        tokens, _, _ = ctrl_lib.sample_arch(cparams, cspec, sub)
        tokens_np = np.asarray(tokens)
        arch = space.tokens_to_arch(tokens_np)
        aa = space.arch_arrays(arch)
        idx = rng.choice(n, size=rbs, replace=False)
        err = float(_child_errors(bank, onehot_pad(idx), jnp.asarray(codes[idx]), aa, space))
        ratio = reward_model.ratio(arch, err)
        return jrng, tokens, arch, aa, err, ratio

    for it in range(1, cfg.total_iters + 1):
        # ---- model training iteration (controller fixed) — Alg. 2 l.5-13
        if it % model_every == 0:
            jrng, tokens, arch, aa, err, ratio = sample_and_score(jrng)
            for _ in range(cfg.model_epochs_per_iter):
                idx = rng.choice(n, size=bs, replace=False)
                bank, bank_opt, loss = _bank_step(
                    bank, bank_opt, onehot_pad(idx), jnp.asarray(codes[idx]), aa,
                    space, cfg.lr_model,
                )
            history.append(
                {"iter": it, "ratio": ratio, "err": err,
                 "child_params": space.child_num_params(arch)}
            )
            if ratio < best["ratio"]:
                best = {"ratio": ratio, "arch": arch}
            if cfg.verbose and it % 10 == 0:
                print(f"[mhas] it={it} loss={float(loss):.4f} err={err:.3f} ratio={ratio:.4f}")
            lf = float(loss)
            if prev_loss is not None and abs(prev_loss - lf) < cfg.early_stop_tol:
                if cfg.verbose:
                    print(f"[mhas] early stop at iter {it}")
                break
            prev_loss = lf

        # ---- controller training iteration (weights fixed) — Alg. 2 l.14-20
        if it % ctrl_every == 0:
            tokens_batch, advantages = [], []
            for _ in range(cfg.controller_samples):
                jrng, tokens, arch, aa, err, ratio = sample_and_score(jrng)
                reward = -ratio
                baseline = (
                    reward
                    if baseline is None
                    else cfg.baseline_decay * baseline + (1 - cfg.baseline_decay) * reward
                )
                tokens_batch.append(tokens)
                advantages.append(reward - baseline)
                history.append(
                    {"iter": it, "ratio": ratio, "err": err,
                     "child_params": space.child_num_params(arch)}
                )
                if ratio < best["ratio"]:
                    best = {"ratio": ratio, "arch": arch}
            cparams, copt, _ = ctrl_update(
                cparams, copt, jnp.stack(tokens_batch), jnp.asarray(advantages)
            )

    if best["arch"] is None:  # degenerate budget: sample one unconditionally
        jrng, tokens, arch, aa, err, ratio = sample_and_score(jrng)
        best = {"ratio": ratio, "arch": arch}

    # ---- finalize: slice the bank, fine-tune the child (paper §V-A6)
    spec = space.child_spec(best["arch"])
    params = space.extract_child_params(bank, best["arch"])
    params, _, _ = trainer_lib.train(
        spec,
        digits,
        codes,
        trainer_lib.TrainConfig(
            batch_size=cfg.model_batch,
            epochs=cfg.finetune_epochs,
            early_stop_tol=cfg.early_stop_tol,
            seed=cfg.seed,
        ),
        params=params,
    )
    return MHASResult(
        spec=spec,
        params=params,
        best_arch=best["arch"],
        best_ratio=best["ratio"],
        history=history,
        space=space,
    )
