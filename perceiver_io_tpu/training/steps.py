"""Jitted train/eval step builders.

The replacement for the reference's Lightning step methods
(``lightning.py:127-177``): each builder returns pure functions
``(state, batch) → (state, metrics)`` that the caller jits (single device) or
pjits over a mesh (SPMD — the DDP replacement; gradient sync becomes a
compiler-inserted psum when the batch axis is sharded).

Batches are dicts of arrays:

- MLM / text:  ``{'token_ids': (B, L) int, 'pad_mask': (B, L) bool[, 'label': (B,) int]}``
- image:       ``{'image': (B, *image_shape) float, 'label': (B,) int}``

Transfer learning (reference ``train_seq_clf.py:18-28``): ``freeze_subtrees``
masks optimizer updates for a params subtree (requires_grad=False parity) and
the classifier steps run a frozen encoder in eval mode (``.eval()`` parity).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax

from perceiver_io_tpu.training.losses import (
    classification_loss_and_accuracy,
    cross_entropy_with_ignore,
    pallas_linear_cross_entropy_with_ignore,
)
from perceiver_io_tpu.training.train_state import TrainState

Array = jax.Array
Metrics = dict
Schedule = Callable[[Array], Array]


def freeze_subtrees(
    tx: optax.GradientTransformation, params, frozen_keys: Sequence[str]
) -> optax.GradientTransformation:
    """Zero out updates for top-level params subtrees named in ``frozen_keys``.

    The functional analogue of the reference's ``freeze()``
    (``train/utils.py:5-8``): frozen params receive no updates but still flow
    through the forward/backward pass.
    """
    frozen = set(frozen_keys)

    def label(tree):
        return {k: ("frozen" if k in frozen else "trainable") for k in tree}

    return optax.multi_transform(
        {"trainable": tx, "frozen": optax.set_to_zero()}, param_labels=label(params)
    )


def _lr_metric(schedule: Optional[Schedule], step: Array) -> dict:
    return {} if schedule is None else {"lr": schedule(step)}


def make_scanned_step(train_step):
    """Wrap a ``(state, batch) → (state, metrics)`` step into a
    ``(state, stacked_batches) → (state, window_metrics)`` multi-step
    dispatch: ``lax.scan`` over a leading K axis of per-step batches.

    One dispatch then covers K optimizer steps — on dispatch-latency-bound
    hosts (very fast steps, a slow or busy host) this amortizes
    the per-call overhead that otherwise gates the whole training loop (how
    large that gap is on a local chip is not measured, PERF.md). Float metrics come back as the window
    mean; integer metrics as the window MAX (for a monotonic counter that is
    its last value, and an any-fired flag — :func:`make_guarded_step`'s
    ``bad_step`` — survives the reduction instead of being masked by a clean
    final sub-step); anything else as the last value.
    """

    def scanned(state, stacked):
        def body(s, b):
            return train_step(s, b)

        state, ms = jax.lax.scan(body, state, stacked)

        def reduce(leaf):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                return leaf.mean(axis=0)
            if jnp.issubdtype(leaf.dtype, jnp.integer):
                return leaf.max(axis=0)
            return leaf[-1]

        return state, jax.tree.map(reduce, ms)

    return scanned


def make_guarded_step(train_step):
    """Collective-consistent non-finite-step guard: wrap a ``(state, batch) →
    (state, metrics)`` step so a non-finite loss SKIPS the update ON DEVICE —
    every leaf of the returned state is selected between the pre-step and
    post-step value by the same device-resident flag, and ``metrics`` gains
    ``bad_step`` (int32 0/1, deliberately non-float so a host-side NaN
    corruption of the fetched metrics cannot forge or erase it).

    This is what lifts the r9 single-process-only restriction on
    ``skip_nonfinite_steps``: under a multi-host data-sharded mesh the loss
    is already the output of the compiler-inserted cross-host psum (a NaN in
    ANY host's batch shard poisons the global scalar for every peer
    identically), so the flag derived from it — and therefore the
    skip-or-keep select — is bit-identical on all hosts by construction. No
    host ever makes a local decision that could desynchronize the fleet's
    collective programs, and no extra host round-trip is spent agreeing.

    Wrap BEFORE :func:`make_scanned_step` so each sub-step of a multi-step
    dispatch window selects independently (a mid-window bad step discards
    only its own update).
    """

    def select(bad, old, new):
        if jax.dtypes.issubdtype(new.dtype, jax.dtypes.prng_key):
            # typed PRNG keys carry an extended dtype jnp.where rejects;
            # select their raw key data and re-wrap
            data = jnp.where(bad, jax.random.key_data(old),
                             jax.random.key_data(new))
            return jax.random.wrap_key_data(
                data, impl=jax.random.key_impl(new))
        return jnp.where(bad, old, new)

    def guarded(state, batch):
        new_state, metrics = train_step(state, batch)
        loss = metrics.get("loss")
        if loss is None:
            # no loss metric = nothing to guard on (the pre-r19 host-side
            # check was a no-op here too); pass through with the flag down
            metrics = dict(metrics)
            metrics["bad_step"] = jnp.int32(0)
            return new_state, metrics
        bad = jnp.logical_not(jnp.all(jnp.isfinite(loss)))
        kept = jax.tree.map(
            lambda old, new: select(bad, old, new), state, new_state)
        metrics = dict(metrics)
        metrics["bad_step"] = bad.astype(jnp.int32)
        return kept, metrics

    return guarded


def mlm_gather_capacity(seq_len: int, mask_p: float = 0.15) -> int:
    """Default masked-decode capacity: 2·mask_p·L rounded up to a multiple of
    32 (sublane-friendly), capped at L. At 2× the expected masked count the
    odds of a row overflowing are negligible (>13σ at the reference config)."""
    cap = -(-int(2 * mask_p * seq_len) // 32) * 32
    return min(seq_len, max(cap, 32))


def _make_steps(loss_fn, rng_streams: Sequence[str], schedule: Optional[Schedule]):
    """The one train step and the one eval step of this module, around
    ``loss_fn(params, batch, rngs, deterministic) -> (loss, aux)`` (``aux`` a
    dict of further metrics, possibly empty).

    - ``train_step(state, batch) -> (state, metrics)`` draws the step's
      ``rng_streams`` from the state (none when the tuple is empty) and
      publishes ``loss``, ``aux`` and, with a schedule, ``lr``.
    - ``eval_step(state, batch, key=None) -> metrics`` runs the loss
      deterministically. ``key`` is the Trainer's stochastic-eval slot: it
      feeds the ``masking`` stream where the family draws one (the val loss
      is then measured on corrupted inputs; without a key the stream is the
      state's own for its step) and is ignored elsewhere.
    """

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Metrics]:
        rngs = state.step_rngs(*rng_streams) if rng_streams else {}
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch, rngs, False
        )
        metrics = {"loss": loss, **aux, **_lr_metric(schedule, state.step)}
        return state.apply_gradients(grads), metrics

    def eval_step(state: TrainState, batch, key: Optional[Array] = None) -> Metrics:
        rngs = {}
        if "masking" in rng_streams:
            rngs["masking"] = state.step_rngs("masking")["masking"] if key is None else key
        loss, aux = loss_fn(state.params, batch, rngs, True)
        return {"loss": loss, **aux}

    return train_step, eval_step


def make_mlm_steps(
    model,
    schedule: Optional[Schedule] = None,
    loss_gather_capacity: Optional[int] = None,
    fused_head: bool | str = False,
):
    """(train_step, eval_step, predict_fn) for a ``PerceiverMLM``.

    - train: masking RNG + dropout, CE over selected positions
      (reference ``lightning.py:127-139``).
    - eval: masking applied with an explicit key (val loss is measured on
      corrupted inputs, as in the reference), dropout off.
    - predict: ``masking=False`` forward returning logits — the
      ``predict_samples`` path (reference ``train_mlm.py:14-35``).

    ``loss_gather_capacity``: decode only the masked positions (up to this many
    per row) in train/eval — gradient-equivalent to the full decode but skips
    most of the dominant vocab-projection FLOPs (see ``PerceiverMLM``). The
    predict path decodes every position unless the caller passes explicit
    ``positions`` (see ``predict_fn``).

    ``fused_head``: ``False`` builds the (B, K, V) logits and takes their CE;
    ``'pallas'`` fuses the vocab projection into the CE on the flash-CE kernel
    (``ops.pallas_ce``: matmul + online-logsumexp + label pick inside ONE
    ``pallas_call``, gradients by blockwise recomputation), so the logits
    never materialize in train/eval. Gradient-equivalent to the unfused path
    (tested); predict is unaffected.
    """
    if fused_head not in (False, "pallas"):
        raise ValueError(
            f"fused_head must be False or 'pallas', got {fused_head!r}"
        )

    def loss_fn(params, batch, rngs, deterministic):
        out, labels = model.apply(
            {"params": params},
            batch["token_ids"],
            batch["pad_mask"],
            rngs=rngs,
            deterministic=deterministic,
            loss_gather_capacity=loss_gather_capacity,
            return_features=bool(fused_head),
        )
        if fused_head:
            # the adapter owns the head layout + class-padding scheme
            kernel, bias = model.decoder.output_adapter.masked_head(
                params["decoder"]["output_adapter"]
            )
            return pallas_linear_cross_entropy_with_ignore(out, kernel, bias, labels), {}
        return cross_entropy_with_ignore(out, labels), {}

    def predict_fn(params, token_ids, pad_mask, positions=None):
        # positions (B, K): decode only those rows of the output-query array
        # — (B, K, vocab) logits instead of (B, L, vocab). The prediction
        # hook passes its (static) [MASK] positions so sample prediction at
        # long context never builds or fetches the full logits tensor.
        logits, _ = model.apply(
            {"params": params}, token_ids, pad_mask, masking=False,
            positions=positions,
        )
        return logits

    return (*_make_steps(loss_fn, ("masking", "dropout"), schedule), predict_fn)


def make_ar_steps(model, schedule: Optional[Schedule] = None,
                  latent_offset: Optional[int] = None):
    """(train_step, eval_step, predict_fn) for a ``PerceiverARLM``.

    Next-token CE over the causal latent window: the dense forward's logits
    row i predicts the token at absolute position ``offset + i + 1``
    (``ops.masking.shift_ar_labels`` — final position and pad targets carry
    ``IGNORE_LABEL``, the same convention MLM's CE uses). No masking RNG —
    causality is structural, not sampled; dropout is the only stochastic
    stream."""

    def loss_fn(params, batch, rngs, deterministic):
        from perceiver_io_tpu.ops.masking import shift_ar_labels

        ids, pad = batch["token_ids"], batch["pad_mask"]
        logits = model.apply(
            {"params": params}, ids, pad, rngs=rngs,
            deterministic=deterministic, latent_offset=latent_offset,
        )
        o = (ids.shape[1] - logits.shape[1] if latent_offset is None
             else latent_offset)
        labels = shift_ar_labels(ids, pad, o)
        return cross_entropy_with_ignore(logits, labels), {}

    def predict_fn(params, token_ids, pad_mask):
        return model.apply({"params": params}, token_ids, pad_mask,
                           latent_offset=latent_offset)

    return (*_make_steps(loss_fn, ("dropout",), schedule), predict_fn)


def make_lm_steps(model, schedule: Optional[Schedule] = None):
    """(train_step, eval_step, predict_fn) for a token-level causal decoder
    (``models.decoder_lm.DecoderLM``): batches ``{'token_ids': (B, T) int,
    'pad_mask': (B, T) bool}``. The loss is the model's own (``model.loss``:
    next-token CE plus the weighted multi-token-prediction term); the step's
    metrics carry its parts (``loss_main``, ``loss_mtp``) and the expert
    layers' routing statistics (``moe_load_max_over_mean``,
    ``moe_local_assignment_pct``, ``moe_dropped_assignments``) and
    ``attention_residuals_kept_pct`` (what the blocks' remat keeps), which
    the Trainer's log boundary turns into registry gauges. Nothing is sampled:
    no rng stream is drawn."""

    def loss_fn(params, batch, rngs, deterministic):
        return model.apply({"params": params}, batch["token_ids"], batch["pad_mask"],
                           method=model.loss)

    def predict_fn(params, token_ids):
        return model.apply({"params": params}, token_ids)

    return (*_make_steps(loss_fn, (), schedule), predict_fn)


# a block's noise level is one of these many steps of [1/NOISE_LEVELS, 1]: U[~1e-3,
# 1] on a grid of powers of two, so that every draw and every comparison with
# it is exact and any implementation of the same draws gets the same mask
NOISE_LEVELS = 1024


def block_diffusion_noise(key: Array, token_ids: Array, pad_mask: Array, block_length: int,
                          mask_id: int) -> Tuple[Array, Array, Array]:
    """``(x_t, weights, masked_pct)`` of a batch of (B, L) ids: each block of
    ``block_length`` tokens draws a level ``t_b`` uniform on ``k /
    NOISE_LEVELS``, k = 1..NOISE_LEVELS; each of its tokens that is not
    padding becomes ``mask_id`` with probability ``t_b``. ``weights`` (float32)
    is ``1 / t_b`` where a token was masked and 0 elsewhere; ``masked_pct`` the
    masked share of the tokens that are not padding, in percent."""
    rows, length = token_ids.shape
    k_level, k_token = jax.random.split(key)
    level = jax.random.randint(k_level, (rows, length // block_length), 1, NOISE_LEVELS + 1)
    level = jnp.repeat(level.astype(jnp.float32) * (1.0 / NOISE_LEVELS), block_length, axis=1)
    masked = (jax.random.uniform(k_token, (rows, length)) < level) & ~pad_mask
    noisy = jnp.where(masked, jnp.asarray(mask_id, token_ids.dtype), token_ids)
    weights = jnp.where(masked, 1.0 / level, 0.0)
    real = jnp.maximum(jnp.sum(~pad_mask, dtype=jnp.float32), 1.0)
    return noisy, weights, 100.0 * jnp.sum(masked, dtype=jnp.float32) / real


def make_block_diffusion_steps(model, schedule: Optional[Schedule] = None):
    """(train_step, eval_step, predict_fn) for a ``DecoderLM`` trained by
    block diffusion (``objective`` 'block_diffusion'): batches as
    :func:`make_lm_steps`'. Each step draws its blocks' noise levels and its
    masked tokens on the device from its ``masking`` stream, under the
    ``bd_noise`` scope (:func:`block_diffusion_noise`), and runs the model
    over ``[x_t ; x]`` (``model.diffusion_loss``); the metrics are the loss,
    ``bd_masked_pct`` and the model's own (the expert layers' statistics,
    ``attention_residuals_kept_pct``). ``predict_fn(params, x_t, x)`` gives
    the noised half's logits."""
    c = model.config

    def loss_fn(params, batch, rngs, deterministic):
        ids = batch["token_ids"]
        with jax.named_scope("bd_noise"):
            noisy, weights, masked_pct = block_diffusion_noise(
                rngs["masking"], ids, batch["pad_mask"], c.block_length, c.mask_token_id)
        loss, metrics = model.apply({"params": params}, noisy, ids, weights,
                                    method=model.diffusion_loss)
        return loss, {**metrics, "bd_masked_pct": masked_pct}

    def predict_fn(params, noisy_ids, token_ids):
        return model.apply({"params": params}, noisy_ids, token_ids,
                           method=model.diffusion_logits)

    return (*_make_steps(loss_fn, ("masking",), schedule), predict_fn)


def make_classifier_steps(
    model,
    schedule: Optional[Schedule] = None,
    input_kind: str = "image",
    frozen_encoder: bool = False,
):
    """(train_step, eval_step) for a ``PerceiverIO`` classifier.

    ``input_kind``: 'image' (no pad mask, reference ``lightning.py:253-255``)
    or 'text' (pad-masked, reference ``lightning.py:209-211``).
    ``frozen_encoder=True`` runs the encoder deterministically (eval-mode
    parity with the reference's freeze+``.eval()``); combine with
    ``freeze_subtrees(tx, params, ['encoder'])`` to stop its updates.
    """
    if input_kind not in ("image", "text"):
        raise ValueError(f"input_kind must be 'image' or 'text', got {input_kind!r}")

    def forward(params, batch, rngs, deterministic):
        kwargs = {"deterministic": deterministic}
        if frozen_encoder:
            kwargs["encoder_deterministic"] = True
        if input_kind == "image":
            return model.apply({"params": params}, batch["image"], rngs=rngs, **kwargs)
        return model.apply(
            {"params": params},
            batch["token_ids"],
            pad_mask=batch["pad_mask"],
            rngs=rngs,
            **kwargs,
        )

    def loss_fn(params, batch, rngs, deterministic):
        logits = forward(params, batch, rngs, deterministic)
        loss, acc = classification_loss_and_accuracy(logits, batch["label"])
        return loss, {"acc": acc}

    return _make_steps(loss_fn, ("dropout",), schedule)


def make_multimodal_steps(
    model,
    schedule: Optional[Schedule] = None,
    video_weight: float = 1.0,
    audio_weight: float = 1.0,
    label_weight: float = 1.0,
):
    """(train_step, eval_step) for the multimodal autoencoder: batches
    ``{'video': (B, T, H, W, C), 'audio': (B, S, C_a), 'label': (B,) int}``,
    loss = weighted MSE(video) + MSE(audio) + CE(label).

    When the model's video head runs in patch space
    (``VideoOutputAdapter.as_patches`` — the ``video_patch_loss`` builder
    knob), the patch geometry is read off the adapter here and the TARGET is
    patchified in the loss instead of the prediction being un-patchified in
    the adapter (exact up to fp reassociation)."""
    from perceiver_io_tpu.models.multimodal import multimodal_autoencoding_loss

    video_patch_info = None
    output_adapter = getattr(
        getattr(model, "decoder", None), "output_adapter", None)
    for name, adapter in getattr(output_adapter, "adapters", ()):
        if name == "video" and getattr(adapter, "as_patches", False):
            video_patch_info = (adapter.grid_shape, adapter.patch_shape)

    def loss_fn(params, batch, rngs, deterministic):
        outputs = model.apply(
            {"params": params},
            {"video": batch["video"], "audio": batch["audio"]},
            rngs=rngs,
            deterministic=deterministic,
        )
        return multimodal_autoencoding_loss(
            outputs, batch, video_weight, audio_weight, label_weight,
            video_patch_info=video_patch_info,
        )

    return _make_steps(loss_fn, ("dropout",), schedule)


def make_flow_steps(model, schedule: Optional[Schedule] = None):
    """(train_step, eval_step) for an optical-flow ``PerceiverIO`` (dense
    2D-query decoder): batches ``{'frames': (B, 2, H, W, C), 'flow':
    (B, H, W, 2)}``, loss = mean end-point error."""
    from perceiver_io_tpu.models.flow import end_point_error

    def loss_fn(params, batch, rngs, deterministic):
        pred = model.apply(
            {"params": params}, batch["frames"], rngs=rngs,
            deterministic=deterministic,
        )
        return end_point_error(pred, batch["flow"]), {}

    return _make_steps(loss_fn, ("dropout",), schedule)
