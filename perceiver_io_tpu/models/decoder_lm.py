"""A token-level causal decoder: ONE skeleton for three public families.

The skeleton: an embedding, a Python loop of rematerialised pre-norm blocks

    h = x + Mixer_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

or, with ``one_sublayer_blocks``, blocks of ONE sublayer each,

    y = x + Sub_l(RMSNorm(x))      Sub_l a mixer or the expert layer

a final RMSNorm, an output head (a matrix of its own, or the embedding's
transpose: ``tie_word_embeddings``), the loss, and the expert layers'
statistics as the step's metrics. What a layer IS is a per-layer fact of the
configuration (:class:`DecoderLMConfig`, ``blocks``): ``layer_types[l]`` names
its token mixer, and its FFN is a dense SwiGLU in the first
``first_k_dense_replace`` layers and the routed expert layer (``ops/moe.py``)
after; in a stack of one-sublayer blocks ``layer_types[l]`` is a mixer or
``'moe'``, the expert layer alone. The mixers:

- ``'mla'``: multi-head latent attention (``ops/latent_attention.py``);
- ``'full_attention'``: grouped-query attention, with per-head RMSNorm of
  queries and keys (``qk_norm``) and half-split rotary (``rotary``) or
  without either (``ops/grouped_query_attention.py``);
- ``'conv'``: the gated short convolution (``ops/short_conv.py``);
- ``'mamba2'``: the Mamba-2 state-space mixer, its recurrence a chunked scan
  (``ops/mamba2.py``: a pair of Pallas kernels on a TPU, ``ops/pallas_ssd.py``,
  XLA einsums elsewhere; the backend decides, no flag).

Each family's published ``config.json`` maps onto the skeleton in a
``from_dict`` of its own, chosen by the published ``model_type``:

- the DeepSeek-V3 family (``joyai_llm_flash`` is one; any ``model_type`` but
  the two below): ``'mla'`` in every layer, a shared expert beside the routed
  ones, an untied head, and one multi-token-prediction module;
- ``lfm2_moe`` (LFM2-8B-A1B): ``layer_types`` mixes ``'conv'`` and
  ``'full_attention'``; ``num_dense_layers`` leading dense layers; 32 experts,
  top 4 of ``sigmoid + bias``, gates normalised with 1e-6, NO shared expert;
  the head tied to the embedding; no MTP module;
- ``nemotron_h`` (the Nemotron-H stack, as Nemotron-Labs-TwoTower-30B-A3B's
  ``config.json`` configures it): one sublayer a block by
  ``hybrid_override_pattern`` (``M`` ``'mamba2'``, ``*`` ``'full_attention'``
  without norm or positions, ``E`` ``'moe'``); non-gated squared-ReLU experts
  (``mlp_hidden_act`` ``relu2``) beside one shared expert of a width of its
  own; an untied head; no MTP module. It is the language model that those keys
  define, trained by next-token cross-entropy: the second (denoiser) tower
  that the model's card describes has no key in that file and nothing here
  stands in for it.

Nothing here knows a width. The expert layers are told which experts this
chip holds (``experts_held``, ``expert_offset``; default all): with a share,
what the absent experts would add is left out and that partial result goes on
to the next layer.

Multi-token prediction (depth ``num_nextn_predict_layers``, 0 or 1 here):

    h'_i = [RMSNorm_e(Emb(t_{i+1})) | RMSNorm_h(h_i)] W_eh

then one more block of the expert kind (its mixer the last layer's), a final
RMSNorm of its own and the SHARED output head; it predicts ``t_{i+2}``.
``h_i`` is the main stack's output after its final norm. The loss is ``mean
CE_main + mtp_loss_factor * mean CE_mtp``; each mean is over its own valid
targets (the last position, the last two for the MTP term, and padding are
ignored).

Compute runs in ``dtype`` (bfloat16 on the chip) with float32 parameters,
router, norms, softmaxes and convolution taps. Each block is rematerialised
(``nn.remat``): its backward pass recomputes the block once from its input.
Where the causal attention is the Pallas kernel, a block with an attention
mixer also keeps what the kernel's backward needs of its forward (the output
and one max and one denominator a row, ``REMAT_FUSED_*`` of
``ops/pallas_attention.py``), as long as those bytes over all the ATTENTION
layers fit the share of the device's memory that
``models.perceiver.remat_keeps`` allows: the recomputation then holds
everything but the kernel (``DecoderLM._remat_policy``; the loss's
``attention_residuals_kept_pct`` is 100 there and 0 elsewhere). The
head-and-loss computations are rematerialised whole, so that no (tokens,
vocabulary) float32 logits wait for the backward pass.

Scopes a device trace is cut by: ``embed``, ``mla_attention``,
``gqa_attention``, ``short_conv``, ``mamba2`` (with ``mamba2/ssd_scan`` around
the scan alone, its backward kernel included), ``moe/*``, ``mtp`` (everything the module runs, its attention
and experts included) and ``head_loss``; no operation of the main stack lies
outside a layer's scope but norms, residual adds and the dense SwiGLU.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from perceiver_io_tpu.models.perceiver import remat_keeps
from perceiver_io_tpu.ops.attention import torch_linear_kernel_init
from perceiver_io_tpu.ops.grouped_query_attention import GroupedQueryAttention
from perceiver_io_tpu.ops.latent_attention import (
    MultiHeadLatentAttention,
    RMSNorm,
    resolve_causal_impl,
)
from perceiver_io_tpu.ops.mamba2 import Mamba2Mixer
from perceiver_io_tpu.ops.masking import IGNORE_LABEL
from perceiver_io_tpu.ops.moe import Kernel, MoELayer, SwiGLU
from perceiver_io_tpu.ops.short_conv import GatedShortConv
from perceiver_io_tpu.training.losses import cross_entropy_with_ignore

Array = jax.Array

MIXERS = ("mla", "full_attention", "conv", "mamba2")
EXPERTS = "moe"  # the routed expert layer: an FFN's kind, and a one-sublayer block's
LFM2_MOE = "lfm2_moe"      # the published ``model_type`` of the second family
NEMOTRON_H = "nemotron_h"  # ... and of the third
# a character of ``hybrid_override_pattern`` -> the block's one sublayer (the
# family's ``-``, a dense MLP block, is in no configuration here)
_PATTERN = {"M": "mamba2", "*": "full_attention", "E": EXPERTS}

# what the published keys must say for this module to be the model they
# describe, by family
_REQUIRED = {
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "rope_interleave": True, "rope_scaling": None, "tie_word_embeddings": False,
    "hidden_act": "silu", "moe_layer_freq": 1, "attention_bias": False,
}
_REQUIRED_LFM2 = {"conv_bias": False, "use_expert_bias": True, "tie_word_embeddings": True}
_REQUIRED_NEMOTRON_H = {
    "use_conv_bias": True, "use_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
    "attention_bias": False, "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "n_group": 1, "topk_group": 1, "tie_word_embeddings": False, "sliding_window": None,
}
# the LFM2 family's names for what the skeleton already has a field for
_LFM2_NAMES = {"num_dense_layers": "first_k_dense_replace", "num_experts": "n_routed_experts",
               "norm_eps": "rms_norm_eps"}
# fields no DeepSeek-V3 ``config.json`` sets, whatever keys of those names it carries
_LFM2_ONLY = ("layer_types", "num_key_value_heads", "head_dim", "conv_L_cache",
                  "gate_eps", "expert_bias_buffer", "tie_word_embeddings")
# ... nor any LFM2 one: the Nemotron-H family's published keys that are fields
# of their own name, and what its ``from_dict`` sets
_NEMOTRON_H_KEYS = ("mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
                    "conv_kernel", "chunk_size", "time_step_min", "time_step_max",
                    "time_step_floor", "mlp_hidden_act", "moe_shared_expert_intermediate_size")
_NEMOTRON_H_ONLY = _NEMOTRON_H_KEYS + ("one_sublayer_blocks", "qk_norm", "rotary")


def _require(config: Dict[str, Any], required: Dict[str, Any]) -> None:
    for key, want in required.items():
        if key in config and config[key] != want:
            raise ValueError(f"{key}={config[key]!r} is not supported (only {want!r})")


@dataclasses.dataclass(frozen=True, kw_only=True)
class DecoderLMConfig:
    """The skeleton's sizes, under the DeepSeek-V3 family's published names
    where it has one for the thing, and the LFM2 and Nemotron-H families' for
    what only they have (``from_dict`` translates the rest)."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int          # the dense SwiGLU of the leading layers
    moe_intermediate_size: int
    num_hidden_layers: int
    first_k_dense_replace: int      # leading layers whose FFN is dense
    num_attention_heads: int
    n_routed_experts: int
    num_experts_per_tok: int
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    gate_eps: float = 1e-20         # added to the selected scores' sum
    expert_bias_buffer: bool = False  # the selection bias is the LFM2 family's buffer (ops/moe.py)
    # the token mixer of every layer, of ``MIXERS``; empty: 'mla' in every layer
    layer_types: Tuple[str, ...] = ()
    # a block is ONE sublayer: ``layer_types[l]`` is a mixer or ``EXPERTS``
    one_sublayer_blocks: bool = False
    # 'mla' layers
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # 'full_attention' layers
    num_key_value_heads: int = 0
    head_dim: int = 0
    qk_norm: bool = True  # per-head RMSNorm of queries and keys
    rotary: bool = True   # rotary position embedding (without: no position encoding at all)
    # 'mamba2' layers: heads, a head's channels, groups of B / C, a head's state
    # is ``mamba_head_dim x ssm_state_size``, taps, the scan's chunk, and the
    # range of the time step's initialisation
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    n_groups: int = 0
    ssm_state_size: int = 0
    conv_kernel: int = 0
    chunk_size: int = 0
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # the experts' form: 'silu' a SwiGLU, 'relu2' the non-gated squared ReLU
    mlp_hidden_act: str = "silu"
    # the shared expert's width; 0: ``n_shared_experts * moe_intermediate_size``
    moe_shared_expert_intermediate_size: int = 0
    # 'conv' layers: the taps of the short convolution
    conv_L_cache: int = 0
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 0
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    # not in a config.json: the chip's share of the experts, and the MTP term's weight
    experts_held: Optional[int] = None
    expert_offset: int = 0
    mtp_loss_factor: float = 0.3

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "DecoderLMConfig":
        """From a published ``config.json``'s keys, by its ``model_type``
        (others are ignored, those a family requires must say what this
        module computes)."""
        if config.get("model_type") == LFM2_MOE:
            return cls._from_lfm2_moe(config)
        if config.get("model_type") == NEMOTRON_H:
            return cls._from_nemotron_h(config)
        _require(config, _REQUIRED)
        names = {f.name for f in dataclasses.fields(cls)} - set(_LFM2_ONLY + _NEMOTRON_H_ONLY)
        return cls(**{k: v for k, v in config.items() if k in names})

    @classmethod
    def _from_lfm2_moe(cls, config: Dict[str, Any]) -> "DecoderLMConfig":
        """LFM2-8B-A1B's keys: ``layer_types`` as published, the head's depth
        ``hidden_size / num_attention_heads``, no shared expert, 1e-6 in the
        gates' normalisation, the head tied to the embedding (the family
        ties; the key is absent from its ``config.json``), no MTP module."""
        _require(config, _REQUIRED_LFM2)
        names = {"vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
                 "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
                 "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
                 "conv_L_cache", "rope_theta", "experts_held", "expert_offset"}
        sizes = {k: v for k, v in config.items() if k in names}
        sizes.update({ours: config[theirs] for theirs, ours in _LFM2_NAMES.items()
                      if theirs in config})
        return cls(layer_types=tuple(config["layer_types"]),
                   head_dim=config["hidden_size"] // config["num_attention_heads"],
                   gate_eps=1e-6, expert_bias_buffer=True, tie_word_embeddings=True, **sizes)

    @classmethod
    def _from_nemotron_h(cls, config: Dict[str, Any]) -> "DecoderLMConfig":
        """The Nemotron-H stack's keys: one sublayer a block by
        ``hybrid_override_pattern``; attention heads of the published
        ``head_dim`` with neither norm nor position encoding (the family's
        public implementation applies none: its ``rope_theta`` and
        ``partial_rotary_factor`` are read by nothing); the Mamba-2 sizes
        under their published names (``expand`` is read by nothing: the
        mixer's inner width is ``mamba_num_heads x mamba_head_dim``);
        ``relu2`` experts and a shared expert of
        ``moe_shared_expert_intermediate_size``; 1e-20 in the gates'
        normalisation; the selection bias a buffer; an untied head."""
        _require(config, _REQUIRED_NEMOTRON_H)
        if tuple(config.get("time_step_limit") or (0, None)) not in ((0, None), (0, float("inf"))):
            raise ValueError(f"time_step_limit={config['time_step_limit']!r} is not supported "
                             "(only (0, inf), which clamps nothing)")
        pattern = config["hybrid_override_pattern"]
        if set(pattern) - set(_PATTERN):
            raise ValueError(f"hybrid_override_pattern {pattern!r}: only blocks of "
                             f"{sorted(_PATTERN)} are supported")
        names = {"vocab_size", "hidden_size", "moe_intermediate_size", "num_attention_heads",
                 "num_key_value_heads", "head_dim", "n_routed_experts", "num_experts_per_tok",
                 "n_shared_experts", "routed_scaling_factor", "norm_topk_prob", "experts_held",
                 "expert_offset", *_NEMOTRON_H_KEYS}
        sizes = {k: v for k, v in config.items() if k in names}
        return cls(layer_types=tuple(_PATTERN[kind] for kind in pattern),
                   num_hidden_layers=config.get("num_hidden_layers", len(pattern)),
                   intermediate_size=config.get("intermediate_size", 0), first_k_dense_replace=0,
                   rms_norm_eps=config["layer_norm_epsilon"], one_sublayer_blocks=True,
                   qk_norm=False, rotary=False, expert_bias_buffer=True, **sizes)

    def __post_init__(self):
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers must be 0 or 1")
        kinds = MIXERS + ((EXPERTS,) if self.one_sublayer_blocks else ())
        if len(self.mixers) != self.num_hidden_layers or set(self.mixers) - set(kinds):
            raise ValueError(f"layer_types {self.layer_types} for {self.num_hidden_layers} "
                             f"layers of {kinds}")
        if "full_attention" in self.mixers and self.num_attention_heads % max(
                self.num_key_value_heads, 1):
            raise ValueError(f"{self.num_attention_heads} query heads over "
                             f"{self.num_key_value_heads} key/value heads")
        if "mamba2" in self.mixers and (self.n_groups < 1 or self.mamba_num_heads % self.n_groups):
            raise ValueError(f"{self.mamba_num_heads} Mamba heads over {self.n_groups} groups")
        if self.one_sublayer_blocks and self.num_nextn_predict_layers:
            raise ValueError("the MTP module is a two-sublayer block")

    @property
    def mixers(self) -> Tuple[str, ...]:
        """``layer_types`` of every layer of the main stack: its token mixer
        (in a stack of one-sublayer blocks, a mixer or ``EXPERTS``)."""
        return self.layer_types or ("mla",) * self.num_hidden_layers

    @property
    def blocks(self) -> Tuple[Tuple[str, str], ...]:
        """``(mixer, ffn)`` of every block of the main stack: a mixer of
        ``MIXERS`` and an FFN, ``'dense'`` or ``EXPERTS``; in a stack of
        one-sublayer blocks one of the two is ``''``."""
        if self.one_sublayer_blocks:
            return tuple(("", EXPERTS) if kind == EXPERTS else (kind, "") for kind in self.mixers)
        return tuple((mixer, EXPERTS if i >= self.first_k_dense_replace else "dense")
                     for i, mixer in enumerate(self.mixers))

    def value_depth(self, mixer: str) -> int:
        """Channels a head of ``mixer``'s causal kernel writes (0: no kernel)."""
        return {"mla": self.v_head_dim, "full_attention": self.head_dim}.get(mixer, 0)


class DecoderBlock(nn.Module):
    config: DecoderLMConfig
    mixer: str  # of ``MIXERS``, or '' (an FFN alone)
    ffn: str    # 'dense' (the SwiGLU), ``EXPERTS`` (the expert layer), or '' (a mixer alone)
    attn_impl: str = "auto"
    expert_impl: str = "auto"
    dtype: Any = jnp.float32

    def _mixer(self) -> nn.Module:
        c = self.config
        if self.mixer == "conv":
            return GatedShortConv(taps=c.conv_L_cache, dtype=self.dtype, name="conv")
        if self.mixer == "mamba2":
            return Mamba2Mixer(
                num_heads=c.mamba_num_heads, head_dim=c.mamba_head_dim, n_groups=c.n_groups,
                state_size=c.ssm_state_size, conv_kernel=c.conv_kernel, chunk_size=c.chunk_size,
                eps=c.rms_norm_eps, time_step_min=c.time_step_min,
                time_step_max=c.time_step_max, time_step_floor=c.time_step_floor,
                dtype=self.dtype, name="mamba")
        if self.mixer == "full_attention":
            return GroupedQueryAttention(
                num_heads=c.num_attention_heads, num_kv_heads=c.num_key_value_heads,
                head_dim=c.head_dim, rope_theta=c.rope_theta, rms_norm_eps=c.rms_norm_eps,
                qk_norm=c.qk_norm, rotary=c.rotary,
                attn_impl=self.attn_impl, dtype=self.dtype, name="attn")
        return MultiHeadLatentAttention(
            num_heads=c.num_attention_heads, q_lora_rank=c.q_lora_rank,
            kv_lora_rank=c.kv_lora_rank, qk_nope_head_dim=c.qk_nope_head_dim,
            qk_rope_head_dim=c.qk_rope_head_dim, v_head_dim=c.v_head_dim,
            rope_theta=c.rope_theta, rms_norm_eps=c.rms_norm_eps,
            attn_impl=self.attn_impl, dtype=self.dtype, name="attn")

    def _ffn(self, x: Array) -> Tuple[Array, dict]:
        c = self.config
        if self.ffn == "dense":
            return SwiGLU(c.intermediate_size, self.dtype, name="mlp")(x), {}
        return MoELayer(
            num_experts=c.n_routed_experts, top_k=c.num_experts_per_tok,
            width=c.moe_intermediate_size, num_shared=c.n_shared_experts,
            shared_width=c.moe_shared_expert_intermediate_size,
            expert_form="swiglu" if c.mlp_hidden_act == "silu" else c.mlp_hidden_act,
            routed_scaling_factor=c.routed_scaling_factor, norm_topk_prob=c.norm_topk_prob,
            gate_eps=c.gate_eps, expert_bias_buffer=c.expert_bias_buffer,
            experts_held=c.experts_held, expert_offset=c.expert_offset,
            expert_impl=self.expert_impl, dtype=self.dtype, name="moe")(x)

    @nn.compact
    def __call__(self, x: Array) -> Tuple[Array, dict]:
        c = self.config

        def norm(name):
            return RMSNorm(c.rms_norm_eps, self.dtype, name=name)

        if not self.ffn:  # ONE sublayer under one norm
            return x + self._mixer()(norm("norm")(x)), {}
        if not self.mixer:
            y, stats = self._ffn(norm("norm")(x))
            return x + y, stats
        h = x + self._mixer()(norm("attn_norm")(x))
        y, stats = self._ffn(norm("ffn_norm")(h))
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
        if not c.tie_word_embeddings:
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
        T x H x the value depth in ``dtype``, and its two float32 statistics a
        row and head, for every block with an attention mixer (the MTP
        module's too; a convolution keeps nothing). The blocked XLA path names
        nothing (its blocks sit under checkpoints of their own)."""
        c = self.config
        mixers = c.mixers + c.mixers[-1:] * c.num_nextn_predict_layers  # the MTP block's too
        depths = [c.value_depth(mixer) for mixer in mixers if c.value_depth(mixer)]
        saved = 0
        if resolve_causal_impl(self.attn_impl) == "pallas":
            saved = sum(b * t * c.num_attention_heads * (
                depth * jnp.dtype(self.dtype).itemsize + 8) for depth in depths)
        if not remat_keeps(saved, b, len(depths)):
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

        def make(mixer, ffn, name):
            return block(c, mixer, ffn, self.attn_impl, self.expert_impl, self.dtype, name=name)

        with jax.named_scope("embed"):
            x = self.embed(token_ids)
        stats = []
        for i, (mixer, ffn) in enumerate(c.blocks):
            x, s = make(mixer, ffn, f"layer_{i}")(x)
            stats.append(s)
        h = self.final_norm(x)
        if not c.num_nextn_predict_layers:
            return h, None, stats, kept
        with jax.named_scope("mtp"):
            with jax.named_scope("embed"):
                following = self.mtp_enorm(self.embed(jnp.roll(token_ids, -1, axis=1)))
            x = self.mtp_eh_proj(jnp.concatenate([following, self.mtp_hnorm(h)], axis=-1))
            x, s = make(c.mixers[-1], EXPERTS, "mtp_block")(x)
            stats.append(s)
            return h, self.mtp_final_norm(x), stats, kept

    def _head(self) -> Array:
        """The output head's matrix: (D, vocab), or tied the embedding itself,
        (vocab, D), which ``_logits`` contracts over its second axis."""
        return self.embed.embedding if self.config.tie_word_embeddings else self.head()

    def _logits(self, h: Array, kernel: Array) -> Array:
        if self.config.tie_word_embeddings:
            return jnp.einsum("btd,vd->btv", h, kernel.astype(self.dtype),
                              preferred_element_type=jnp.float32)
        return jnp.dot(h, kernel.astype(self.dtype), preferred_element_type=jnp.float32)

    def __call__(self, token_ids: Array) -> Tuple[Array, Optional[Array]]:
        """(B, T) ids -> float32 logits ``(main, mtp)``, each (B, T, vocab):
        ``main[:, i]`` scores ``t_{i+1}``, ``mtp[:, i]`` scores ``t_{i+2}``."""
        h, h_mtp, _, _ = self.hidden_states(token_ids)
        kernel = self._head()
        return self._logits(h, kernel), None if h_mtp is None else self._logits(h_mtp, kernel)

    def loss(self, token_ids: Array, pad_mask: Optional[Array] = None) -> Tuple[Array, dict]:
        """The two-term loss and the step's metrics (float32 scalars)."""
        h, h_mtp, stats, kept = self.hidden_states(token_ids)
        kernel = self._head()

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
