"""Optimizer and LR-schedule factory.

Mirrors the reference optimizer surface (``lightning.py:50-79``): the
reference resolves ``--optimizer`` with ``getattr(torch.optim, name)``
(``lightning.py:60``), so any torch optimizer name works from its CLI. Here
the common names — Adam, AdamW, SGD, RMSprop, Adagrad, Adamax, NAdam,
RAdam — map to optax with torch's exact update semantics; unknown names
raise a loud error listing the supported set (a silent near-miss optimizer
is worse than a loud gap).

Semantic parity notes:

- torch ``Adam(weight_decay=w)`` is *coupled* L2: ``grad += w * param`` before
  the moment updates → ``optax.chain(add_decayed_weights, scale_by_adam, lr)``.
- torch ``AdamW(weight_decay=w)`` is decoupled, decay scaled by the lr →
  ``optax.adamw``.
- torch ``SGD(momentum=m)`` keeps ``buf = m·buf + grad`` (dampening 0) and
  steps by ``lr·buf`` → ``optax.trace(decay=m)``; weight decay is coupled L2
  applied before the momentum buffer.
- torch ``RMSprop``: ``sq = α·sq + (1−α)·g²``, step ``lr·g/(√sq + eps)`` with
  α=0.99, eps=1e-8 — the eps sits OUTSIDE the sqrt →
  ``optax.scale_by_rms(decay=0.99, eps=1e-8, eps_in_sqrt=False)``.
- torch ``Adagrad``: ``sum += g²``, step ``lr·g/(√sum + eps)`` with eps=1e-10
  and zero initial accumulator. optax's ``scale_by_rss`` puts eps inside the
  sqrt and special-cases sum==0, so ``_scale_by_adagrad_torch`` below
  reproduces the torch update directly.
- torch ``OneCycleLR(max_lr, pct_start, total_steps, cycle_momentum=False)``
  uses cosine annealing with ``div_factor=25``, ``final_div_factor=1e4``, a
  peak at step ``pct_start*total_steps - 1`` and the minimum at step
  ``total_steps - 1`` (one-shifted vs. ``optax.cosine_onecycle_schedule``) —
  reproduced exactly by ``torch_one_cycle_schedule`` below.

The schedule callable is returned alongside the transformation so steps can
log the current LR (the reference's per-step ``LearningRateMonitor``,
``train/utils.py:16-17``).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from perceiver_io_tpu import obs

with obs.span("import", module="optax"):
    import optax


def torch_one_cycle_schedule(
    total_steps: int,
    max_lr: float,
    pct_start: float = 0.1,
    div_factor: float = 25.0,
    final_div_factor: float = 1e4,
) -> Callable:
    """Cosine OneCycle with torch's exact phase boundaries.

    initial = max_lr/div_factor; min = initial/final_div_factor; cosine-anneal
    initial→max over steps [0, pct_start*total-1], then max→min over
    [pct_start*total-1, total-1]. jit-friendly (pure jnp on the step counter).
    """
    initial_lr = max_lr / div_factor
    min_lr = initial_lr / final_div_factor
    peak_step = max(pct_start * total_steps - 1.0, 1e-8)
    down_steps = max(total_steps - 1.0 - peak_step, 1e-8)

    def cos_anneal(start, end, frac):
        return end + (start - end) * (1.0 + jnp.cos(jnp.pi * frac)) / 2.0

    def schedule(step):
        s = jnp.asarray(step, jnp.float32)
        up = cos_anneal(initial_lr, max_lr, jnp.clip(s / peak_step, 0.0, 1.0))
        down = cos_anneal(max_lr, min_lr, jnp.clip((s - peak_step) / down_steps, 0.0, 1.0))
        return jnp.where(s <= peak_step, up, down)

    return schedule


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Reference optimizer argparse group (``lightning.py:50-57``)."""

    optimizer: str = "Adam"  # any name make_optimizer maps (Adam, AdamW, SGD,
    # RMSprop, Adagrad, Adamax, NAdam, RAdam — torch-exact semantics each)
    learning_rate: float = 1e-3
    weight_decay: float = 0.0
    one_cycle_lr: bool = False
    one_cycle_pct_start: float = 0.1
    max_steps: Optional[int] = None
    # torch SGD momentum (the reference never sets it — its getattr call
    # passes only lr/weight_decay — but torch's default surface has it)
    momentum: float = 0.0
    # TPU-framework extensions beyond the reference surface:
    grad_clip_norm: Optional[float] = None  # global-norm clipping before moments
    accumulate_steps: int = 1  # micro-batches averaged per optimizer update


class _AdagradState(NamedTuple):
    sum_of_squares: object


def _scale_by_adagrad_torch(
    eps: float = 1e-10, initial_accumulator_value: float = 0.0
) -> optax.GradientTransformation:
    """torch ``Adagrad``'s exact scaling: ``sum += g²; g / (sqrt(sum) + eps)``.

    optax's ``scale_by_rss`` differs in two observable ways (eps inside the
    sqrt; a where() that zeroes updates while the accumulator is zero), so the
    torch update is implemented directly. State mirrors the param-tree paths
    like Adam's moments, so the ZeRO sharding rules apply unchanged.
    """

    def init_fn(params):
        return _AdagradState(
            sum_of_squares=jax.tree.map(
                lambda p: jnp.full_like(p, initial_accumulator_value), params
            )
        )

    def update_fn(updates, state, params=None):
        del params
        sums = jax.tree.map(
            lambda g, s: s + jnp.square(g), updates, state.sum_of_squares
        )
        updates = jax.tree.map(
            lambda g, s: g / (jnp.sqrt(s) + eps), updates, sums
        )
        return updates, _AdagradState(sum_of_squares=sums)

    return optax.GradientTransformation(init_fn, update_fn)


def _scale_by_rms_torch(
    decay: float = 0.99, eps: float = 1e-8
) -> optax.GradientTransformation:
    """torch ``RMSprop``'s exact scaling: ``nu = α·nu + (1-α)·g²;
    g / (sqrt(nu) + eps)`` — eps OUTSIDE the sqrt.

    The optax spelling is ``scale_by_rms(..., eps_in_sqrt=False)``, but the
    optax build this runs under predates that kwarg, so the torch update is
    implemented directly. State reuses ``optax.ScaleByRmsState`` (same
    ``nu`` param-tree mirror), so checkpoints and the ZeRO sharding rules
    are unchanged.
    """

    def init_fn(params):
        return optax.ScaleByRmsState(
            nu=jax.tree.map(jnp.zeros_like, params)
        )

    def update_fn(updates, state, params=None):
        del params
        nu = jax.tree.map(
            lambda g, n: decay * n + (1.0 - decay) * jnp.square(g),
            updates, state.nu,
        )
        updates = jax.tree.map(
            lambda g, n: g / (jnp.sqrt(n) + eps), updates, nu
        )
        return updates, optax.ScaleByRmsState(nu=nu)

    return optax.GradientTransformation(init_fn, update_fn)


class _MomentState(NamedTuple):
    count: object
    mu: object
    nu: object


def _scale_by_adamax_torch(
    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8
) -> optax.GradientTransformation:
    """torch ``Adamax``'s exact scaling (``torch/optim/adamax.py``):
    ``mu = b1*mu + (1-b1)*g``; ``nu = max(b2*nu, |g| + eps)`` (eps inside the
    max, so nu is never zero); step ``mu / ((1 - b1^t) * nu)``."""

    def init_fn(params):
        zeros = jax.tree.map(jnp.zeros_like, params)
        return _MomentState(count=jnp.zeros([], jnp.int32), mu=zeros,
                            nu=jax.tree.map(jnp.zeros_like, params))

    def update_fn(updates, state, params=None):
        del params
        count = state.count + 1
        mu = jax.tree.map(lambda g, m: b1 * m + (1 - b1) * g,
                          updates, state.mu)
        nu = jax.tree.map(
            lambda g, n: jnp.maximum(b2 * n, jnp.abs(g) + eps),
            updates, state.nu,
        )
        bc = 1 - b1 ** count.astype(jnp.float32)
        updates = jax.tree.map(lambda m, n: m / (bc * n), mu, nu)
        return updates, _MomentState(count=count, mu=mu, nu=nu)

    return optax.GradientTransformation(init_fn, update_fn)


class _NAdamState(NamedTuple):
    count: object
    mu_product: object
    mu: object
    nu: object


def _scale_by_nadam_torch(
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    momentum_decay: float = 4e-3,
) -> optax.GradientTransformation:
    """torch ``NAdam``'s exact scaling (``torch/optim/nadam.py``) — Nesterov
    momentum with the 0.96^(t·ψ) momentum-decay schedule torch adds on top of
    Dozat's formulation (optax's ``nesterov=True`` Adam lacks it):
    ``µ_t = b1·(1 − ½·0.96^(t·ψ))``, running ``µ_product``, and the step
    mixes the raw gradient and the first moment, each with its own
    bias-correction, over ``sqrt(nu/(1−b2^t)) + eps``."""

    def init_fn(params):
        return _NAdamState(
            count=jnp.zeros([], jnp.int32),
            mu_product=jnp.ones([], jnp.float32),
            mu=jax.tree.map(jnp.zeros_like, params),
            nu=jax.tree.map(jnp.zeros_like, params),
        )

    def update_fn(updates, state, params=None):
        del params
        count = state.count + 1
        t = count.astype(jnp.float32)
        mu_t = b1 * (1 - 0.5 * 0.96 ** (t * momentum_decay))
        mu_next = b1 * (1 - 0.5 * 0.96 ** ((t + 1) * momentum_decay))
        mu_product = state.mu_product * mu_t
        mu = jax.tree.map(lambda g, m: b1 * m + (1 - b1) * g,
                          updates, state.mu)
        nu = jax.tree.map(lambda g, n: b2 * n + (1 - b2) * jnp.square(g),
                          updates, state.nu)
        bc2 = 1 - b2 ** t
        g_scale = (1 - mu_t) / (1 - mu_product)
        m_scale = mu_next / (1 - mu_product * mu_next)
        updates = jax.tree.map(
            lambda g, m, n: (g_scale * g + m_scale * m)
            / (jnp.sqrt(n / bc2) + eps),
            updates, mu, nu,
        )
        return updates, _NAdamState(count=count, mu_product=mu_product,
                                    mu=mu, nu=nu)

    return optax.GradientTransformation(init_fn, update_fn)


def _scale_by_radam_torch(
    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8
) -> optax.GradientTransformation:
    """torch ``RAdam``'s exact scaling (``torch/optim/radam.py``): Adam
    moments, and while the variance-rectification term ``rho_t <= 5`` the
    step is the bias-corrected first moment ALONE (no second-moment
    denominator); afterwards the rectified adaptive step divides by
    ``sqrt(nu) + eps`` scaled by ``sqrt(1 - b2^t)`` (eps OUTSIDE the
    bias-corrected sqrt — a visible difference from optax's radam)."""
    rho_inf = 2.0 / (1.0 - b2) - 1.0

    def init_fn(params):
        return _MomentState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree.map(jnp.zeros_like, params),
            nu=jax.tree.map(jnp.zeros_like, params),
        )

    def update_fn(updates, state, params=None):
        del params
        count = state.count + 1
        t = count.astype(jnp.float32)
        mu = jax.tree.map(lambda g, m: b1 * m + (1 - b1) * g,
                          updates, state.mu)
        nu = jax.tree.map(lambda g, n: b2 * n + (1 - b2) * jnp.square(g),
                          updates, state.nu)
        # -expm1(t·log b2) keeps 1 - b2^t fully precise in f32 at small t
        # (the naive form loses ~half the mantissa exactly where the
        # rectification boundary sits; torch does this math in python f64)
        bc1 = -jnp.expm1(t * jnp.log(jnp.float32(b1)))
        bc2 = -jnp.expm1(t * jnp.log(jnp.float32(b2)))
        rho_t = rho_inf - 2 * t * (b2 ** t) / bc2
        rect = jnp.sqrt(
            jnp.clip(
                (rho_t - 4) * (rho_t - 2) * rho_inf
                / ((rho_inf - 4) * (rho_inf - 2) * rho_t),
                0.0,
            )
        )
        rectified = rho_t > 5.0

        def leaf(m, n):
            m_hat = m / bc1
            adaptive = m_hat * rect * jnp.sqrt(bc2) / (jnp.sqrt(n) + eps)
            return jnp.where(rectified, adaptive, m_hat)

        updates = jax.tree.map(leaf, mu, nu)
        return updates, _MomentState(count=count, mu=mu, nu=nu)

    return optax.GradientTransformation(init_fn, update_fn)


def make_optimizer(
    config: OptimizerConfig,
) -> Tuple[optax.GradientTransformation, Callable[[int], float]]:
    """Build (transformation, lr_schedule) from the config.

    Raises ValueError when OneCycle is requested without ``max_steps``
    (reference ``lightning.py:65-67``).
    """
    k = config.accumulate_steps
    if k < 1:
        raise ValueError(f"accumulate_steps must be >= 1, got {k}")

    if config.one_cycle_lr:
        if config.max_steps is None:
            raise ValueError("OneCycleLR requires a max_steps value")
        # max_steps counts trainer (micro) steps; the schedule advances once
        # per optimizer update, i.e. every k micro steps
        schedule = torch_one_cycle_schedule(
            total_steps=max(config.max_steps // k, 1),
            max_lr=config.learning_rate,
            pct_start=config.one_cycle_pct_start,
        )
    else:
        schedule = optax.constant_schedule(config.learning_rate)

    name = config.optimizer
    # coupled L2 (torch's default weight_decay semantics for everything but
    # AdamW): grad += wd * param BEFORE any moment/accumulator update
    coupled_wd = (
        [optax.add_decayed_weights(config.weight_decay)]
        if config.weight_decay
        else []
    )
    if name == "Adam":
        tx = optax.chain(
            *coupled_wd,
            optax.scale_by_adam(),
            optax.scale_by_learning_rate(schedule),
        )
    elif name == "AdamW":
        tx = optax.adamw(schedule, weight_decay=config.weight_decay)
    elif name == "SGD":
        momentum = (
            [optax.trace(decay=config.momentum)] if config.momentum else []
        )
        tx = optax.chain(
            *coupled_wd, *momentum, optax.scale_by_learning_rate(schedule)
        )
    elif name == "RMSprop":
        # torch defaults: alpha=0.99, eps=1e-8, eps OUTSIDE the sqrt
        tx = optax.chain(
            *coupled_wd,
            _scale_by_rms_torch(decay=0.99, eps=1e-8),
            optax.scale_by_learning_rate(schedule),
        )
    elif name == "Adagrad":
        tx = optax.chain(
            *coupled_wd,
            _scale_by_adagrad_torch(),
            optax.scale_by_learning_rate(schedule),
        )
    elif name == "Adamax":
        # torch default weight_decay semantics: coupled L2
        tx = optax.chain(
            *coupled_wd,
            _scale_by_adamax_torch(),
            optax.scale_by_learning_rate(schedule),
        )
    elif name == "NAdam":
        # torch NAdam(decoupled_weight_decay=False) default: coupled L2
        tx = optax.chain(
            *coupled_wd,
            _scale_by_nadam_torch(),
            optax.scale_by_learning_rate(schedule),
        )
    elif name == "RAdam":
        # torch RAdam(decoupled_weight_decay=False) default: coupled L2
        tx = optax.chain(
            *coupled_wd,
            _scale_by_radam_torch(),
            optax.scale_by_learning_rate(schedule),
        )
    else:
        raise ValueError(
            f"unknown optimizer {name!r}: this maps torch.optim names to "
            f"optax with torch-exact update semantics, and supports 'Adam', "
            f"'AdamW', 'SGD', 'RMSprop', 'Adagrad', 'Adamax', 'NAdam', "
            f"'RAdam' (the reference resolves ANY torch.optim name via "
            f"getattr, lightning.py:60 — for another name, add a mapping in "
            f"training/optim.py; see docs/MIGRATION.md)"
        )

    if config.grad_clip_norm is not None:
        if config.grad_clip_norm <= 0:
            raise ValueError(f"grad_clip_norm must be > 0, got {config.grad_clip_norm}")
        tx = optax.chain(optax.clip_by_global_norm(config.grad_clip_norm), tx)

    if k > 1:
        ms = optax.MultiSteps(tx, every_k_schedule=k)
        # plain GradientTransformation view, so downstream wrappers
        # (freeze_subtrees' multi_transform) compose with it
        tx = optax.GradientTransformation(ms.init, ms.update)
        micro_schedule = schedule
        schedule = lambda step: micro_schedule(jnp.asarray(step) // k)

    return tx, schedule
