"""A token-level causal decoder: latent attention, routed experts, and a
multi-token-prediction module (the DeepSeek-V3 family of public
``config.json``s; ``joyai_llm_flash`` is one).

Every size is a field of :class:`DecoderLMConfig`, named as the published
``config.json`` names it; nothing here knows a width. Pre-norm blocks:

    h = x + MLA(RMSNorm(x));   y = h + FFN(RMSNorm(h))

``FFN`` is a dense SwiGLU in the first ``first_k_dense_replace`` layers and
the routed expert layer (``ops/moe.py``) after; attention is
``ops/latent_attention.py`` in every layer. The expert layers are told which
experts this chip holds (``experts_held``, ``expert_offset``; default all):
with a share, what the absent experts would add is left out and that partial
result goes on to the next layer.

Multi-token prediction (depth ``num_nextn_predict_layers``, 0 or 1 here):

    h'_i = [RMSNorm_e(Emb(t_{i+1})) | RMSNorm_h(h_i)] W_eh

then one more block of the expert kind, a final RMSNorm of its own and the
SHARED output head; it predicts ``t_{i+2}``. ``h_i`` is the main stack's
output after its final norm. The loss is ``mean CE_main + mtp_loss_factor *
mean CE_mtp``; each mean is over its own valid targets (the last position, the
last two for the MTP term, and padding are ignored).

Compute runs in ``dtype`` (bfloat16 on the chip) with float32 parameters,
router, norms and softmaxes. Each block is rematerialised (``nn.remat``): its
backward pass recomputes the block once from its input. Where the causal
attention is the Pallas kernel, the block also keeps what the kernel's
backward needs of its forward (the output and one max and one denominator a
row, ``REMAT_FUSED_*`` of ``ops/pallas_attention.py``), as long as those bytes
over all layers fit the share of the device's memory that
``models.perceiver.remat_keeps`` allows: the recomputation then holds
everything but the kernel (``DecoderLM._remat_policy``; the loss's
``attention_residuals_kept_pct`` is 100 there and 0 elsewhere). The two
head-and-loss computations are rematerialised whole, so that no (tokens,
vocabulary) float32 logits wait for the backward pass.

Scopes a device trace is cut by: ``embed``, ``mla_attention``, ``moe/*``,
``mtp`` (everything the module runs, its attention and experts included) and
``head_loss``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from perceiver_io_tpu.models.perceiver import remat_keeps
from perceiver_io_tpu.ops.attention import torch_linear_kernel_init
from perceiver_io_tpu.ops.latent_attention import (
    MultiHeadLatentAttention,
    RMSNorm,
    resolve_causal_impl,
)
from perceiver_io_tpu.ops.masking import IGNORE_LABEL
from perceiver_io_tpu.ops.moe import Kernel, MoELayer, SwiGLU
from perceiver_io_tpu.training.losses import cross_entropy_with_ignore

Array = jax.Array

# what the published keys must say for this module to be the model they describe
_REQUIRED = {
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "rope_interleave": True, "rope_scaling": None, "tie_word_embeddings": False,
    "hidden_act": "silu", "moe_layer_freq": 1, "attention_bias": False,
}


@dataclasses.dataclass(frozen=True)
class DecoderLMConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    num_nextn_predict_layers: int = 0
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    # not in a config.json: the chip's share of the experts, and the MTP term's weight
    experts_held: Optional[int] = None
    expert_offset: int = 0
    mtp_loss_factor: float = 0.3

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "DecoderLMConfig":
        """From a published ``config.json``'s keys (others are ignored, those
        of ``_REQUIRED`` must say what this module computes)."""
        for key, want in _REQUIRED.items():
            if key in config and config[key] != want:
                raise ValueError(f"{key}={config[key]!r} is not supported (only {want!r})")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in config.items() if k in names})

    def __post_init__(self):
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers must be 0 or 1")


class DecoderBlock(nn.Module):
    config: DecoderLMConfig
    routed: bool
    attn_impl: str = "auto"
    expert_impl: str = "auto"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Tuple[Array, dict]:
        c = self.config

        def norm(name):
            return RMSNorm(c.rms_norm_eps, self.dtype, name=name)

        h = x + MultiHeadLatentAttention(
            num_heads=c.num_attention_heads, q_lora_rank=c.q_lora_rank,
            kv_lora_rank=c.kv_lora_rank, qk_nope_head_dim=c.qk_nope_head_dim,
            qk_rope_head_dim=c.qk_rope_head_dim, v_head_dim=c.v_head_dim,
            rope_theta=c.rope_theta, rms_norm_eps=c.rms_norm_eps,
            attn_impl=self.attn_impl, dtype=self.dtype, name="attn")(norm("attn_norm")(x))
        if not self.routed:
            return h + SwiGLU(c.intermediate_size, self.dtype, name="mlp")(norm("ffn_norm")(h)), {}
        y, stats = MoELayer(
            num_experts=c.n_routed_experts, top_k=c.num_experts_per_tok,
            width=c.moe_intermediate_size, num_shared=c.n_shared_experts,
            routed_scaling_factor=c.routed_scaling_factor, norm_topk_prob=c.norm_topk_prob,
            experts_held=c.experts_held, expert_offset=c.expert_offset,
            expert_impl=self.expert_impl, dtype=self.dtype, name="moe")(norm("ffn_norm")(h))
        return h + y, stats


class DecoderLM(nn.Module):
    config: DecoderLMConfig
    attn_impl: str = "auto"    # 'auto' | 'pallas' | 'xla' (ops/latent_attention.py)
    expert_impl: str = "auto"  # 'auto' | 'pallas' | 'xla' (ops/moe.py)
    dtype: Any = jnp.float32

    def setup(self):
        c = self.config
        self.embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                              embedding_init=nn.initializers.normal(0.02), name="embed")
        self.final_norm = RMSNorm(c.rms_norm_eps, self.dtype, name="final_norm")
        self.head = Kernel((c.hidden_size, c.vocab_size), name="head")
        if c.num_nextn_predict_layers:
            self.mtp_enorm = RMSNorm(c.rms_norm_eps, self.dtype, name="mtp_enorm")
            self.mtp_hnorm = RMSNorm(c.rms_norm_eps, self.dtype, name="mtp_hnorm")
            self.mtp_eh_proj = nn.Dense(c.hidden_size, use_bias=False, dtype=self.dtype,
                                        kernel_init=torch_linear_kernel_init, name="mtp_eh_proj")
            self.mtp_final_norm = RMSNorm(c.rms_norm_eps, self.dtype, name="mtp_final_norm")

    def _remat_policy(self, b: int, t: int):
        """The ``jax.checkpoint`` policy of the blocks for (B, T) ids; None
        recomputes whole blocks (the bare ``nn.remat``). Decided once per
        trace, from shapes and the device (``remat_keeps``).

        Kept where the causal attention is the Pallas kernel: its output, B x
        T x H x ``v_head_dim`` in ``dtype``, and its two float32 statistics a
        row and head, for every block (the MTP module's too). The blocked XLA
        path names nothing (its blocks sit under checkpoints of their own)."""
        c = self.config
        layers = c.num_hidden_layers + c.num_nextn_predict_layers
        saved = 0
        if resolve_causal_impl(self.attn_impl) == "pallas":
            saved = layers * b * t * c.num_attention_heads * (
                c.v_head_dim * jnp.dtype(self.dtype).itemsize + 8)
        if not remat_keeps(saved, b, layers):
            return None
        from perceiver_io_tpu.ops.pallas_attention import REMAT_FUSED_OUT, REMAT_FUSED_STATS

        return jax.checkpoint_policies.save_only_these_names(REMAT_FUSED_OUT, REMAT_FUSED_STATS)

    @nn.compact
    def hidden_states(self, token_ids: Array) -> Tuple[Array, Optional[Array], list, bool]:
        """``(h, h_mtp, stats, kept)``: the main stack's and the MTP module's
        outputs after their final norms (``h_mtp`` None without the module),
        the expert layers' statistics, one dict a layer, and whether the
        blocks keep the causal kernel's residuals (``_remat_policy``)."""
        c = self.config
        policy = self._remat_policy(*token_ids.shape)
        kept = policy is not None
        block = nn.remat(DecoderBlock, policy=policy)

        def make(routed, name):
            return block(c, routed, self.attn_impl, self.expert_impl, self.dtype, name=name)

        with jax.named_scope("embed"):
            x = self.embed(token_ids)
        stats = []
        for i in range(c.num_hidden_layers):
            x, s = make(i >= c.first_k_dense_replace, f"layer_{i}")(x)
            stats.append(s)
        h = self.final_norm(x)
        if not c.num_nextn_predict_layers:
            return h, None, stats, kept
        with jax.named_scope("mtp"):
            with jax.named_scope("embed"):
                following = self.mtp_enorm(self.embed(jnp.roll(token_ids, -1, axis=1)))
            x = self.mtp_eh_proj(jnp.concatenate([following, self.mtp_hnorm(h)], axis=-1))
            x, s = make(True, "mtp_block")(x)
            stats.append(s)
            return h, self.mtp_final_norm(x), stats, kept

    def _logits(self, h: Array, kernel: Array) -> Array:
        return jnp.dot(h, kernel.astype(self.dtype), preferred_element_type=jnp.float32)

    def __call__(self, token_ids: Array) -> Tuple[Array, Optional[Array]]:
        """(B, T) ids -> float32 logits ``(main, mtp)``, each (B, T, vocab):
        ``main[:, i]`` scores ``t_{i+1}``, ``mtp[:, i]`` scores ``t_{i+2}``."""
        h, h_mtp, _, _ = self.hidden_states(token_ids)
        kernel = self.head()
        return self._logits(h, kernel), None if h_mtp is None else self._logits(h_mtp, kernel)

    def loss(self, token_ids: Array, pad_mask: Optional[Array] = None) -> Tuple[Array, dict]:
        """The two-term loss and the step's metrics (float32 scalars)."""
        h, h_mtp, stats, kept = self.hidden_states(token_ids)
        kernel = self.head()

        def head_loss(hidden, kernel, labels):
            with jax.named_scope("head_loss"):
                return cross_entropy_with_ignore(self._logits(hidden, kernel), labels)

        head_loss = jax.checkpoint(head_loss)
        loss_main = head_loss(h, kernel, next_token_labels(token_ids, pad_mask, 1))
        metrics = {"loss_main": loss_main,
                   "attention_residuals_kept_pct": jnp.float32(100.0 if kept else 0.0)}
        loss = loss_main
        if h_mtp is not None:
            with jax.named_scope("mtp"):
                loss_mtp = head_loss(h_mtp, kernel, next_token_labels(token_ids, pad_mask, 2))
            metrics["loss_mtp"] = loss_mtp
            loss = loss + self.config.mtp_loss_factor * loss_mtp
        routed = [s for s in stats if s]
        for name in (routed[0] if routed else ()):
            reduce = jnp.sum if name.startswith("dropped") else jnp.mean
            metrics[f"moe_{name}"] = reduce(jnp.stack([s[name] for s in routed]))
        return loss, metrics


def next_token_labels(token_ids: Array, pad_mask: Optional[Array], ahead: int) -> Array:
    """Labels of the prediction ``ahead`` tokens on: position i gets
    ``t_{i+ahead}``, and ``IGNORE_LABEL`` where no such token exists (the
    row's last ``ahead`` positions) or it is padding."""
    t = token_ids.shape[1]
    labels = jnp.roll(token_ids, -ahead, axis=1).astype(jnp.int32)
    invalid = jnp.broadcast_to(jnp.arange(t)[None, :] >= t - ahead, token_ids.shape)
    if pad_mask is not None:
        invalid = invalid | jnp.roll(pad_mask, -ahead, axis=1)
    return jnp.where(invalid, IGNORE_LABEL, labels)
