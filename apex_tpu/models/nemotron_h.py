"""A decoder whose blocks have ONE branch: ``x + Mixer_l(RMSNorm_l(x))``,
the mixer of a block a Mamba-2 state-space mixer, a position-free attention
or a routed-expert layer, as the letters of ``hybrid_override_pattern`` say
(``M``, ``*``, ``E``; the ``nemotron_h`` family's published config keys).
The parts are the per-layer decoder's (``models/laguna.py``: the attention
layer, here with no rotation of any kind; ``parallel.ExpertParallelMLP``, here
with non-gated squared-ReLU experts and a shared expert of the same kind; the
embedding, the final norm, the untied head and the fused chunked loss) and
``transformer.Mamba2Mixer``; this module is the configuration and the block.

- ``M``: ``Mamba2Mixer`` (module ``mamba``): ``mamba_num_heads`` heads of
  ``mamba_head_dim``, state ``ssm_state_size``, ``n_groups`` groups of B and
  C, ``conv_kernel`` taps with a bias, the scan in chunks of ``chunk_size``.
- ``*``: ``LagunaAttention`` (module ``self_attn``): ``num_attention_heads``
  query heads over ``num_key_value_heads`` K/V heads of ``head_dim``, causal,
  no window, no rotation, no gate, no QK-norm.
- ``E``: ``ExpertParallelMLP`` (module ``mlp``): a sigmoid router over the
  published ``router_experts`` with a selection bias, the
  ``num_experts_per_tok`` largest renormalized and scaled by
  ``routed_scaling_factor``, ``relu2`` experts of ``moe_intermediate_size``
  (the ``n_routed_experts`` held here, from ``experts_held_start``) and one
  shared expert of ``moe_shared_expert_intermediate_size``.

Training and full-sequence forward only: a cache would have to hold recurrent
state beside keys and values (ROADMAP, Reach).
"""

from __future__ import annotations

import inspect

from .. import nn
from ..parallel.expert_parallel import ExpertParallelMLP
from ..transformer.mamba2 import Mamba2Mixer
from ._remat import _MODES
from .laguna import FULL, Laguna, LagunaAttention
from .llama import RMSNorm

__all__ = ["NemotronHConfig", "NemotronH"]

MAMBA, MOE = "mamba", "moe"
_KINDS = {"M": MAMBA, "*": FULL, "E": MOE}


class NemotronHConfig:
    """Sizes from the family's keys.  ``n_routed_experts`` experts are HELD
    here, from ``experts_held_start``, of the ``router_experts`` the router
    scores (a file's ``num_experts_published``; default: all are held).  The
    attributes ``LagunaAttention`` and ``Laguna`` read of a configuration are
    stated as what this family is (one head count, no rotation, no gate, no
    loop, an untied head)."""

    def __init__(self, vocab_size, hidden_size, hybrid_override_pattern,
                 mamba_num_heads, mamba_head_dim, ssm_state_size, n_groups,
                 num_attention_heads, num_key_value_heads, head_dim,
                 n_routed_experts=None, num_experts_per_tok=None,
                 moe_intermediate_size=None,
                 moe_shared_expert_intermediate_size=0,
                 routed_scaling_factor=1.0, router_experts=None,
                 experts_held_start=0, moe_row_buffer_factor=None,
                 conv_kernel=4, chunk_size=128, use_conv_bias=True,
                 mlp_hidden_act="relu2", norm_eps=1e-5,
                 norm_topk_prob=True, max_position_embeddings=8192,
                 remat=None, head_chunk=8192):
        # what the family's files all state, and the one form that is built
        if not use_conv_bias or mlp_hidden_act != "relu2":
            raise ValueError("the convolution has a bias and the experts are "
                             f"relu2: got use_conv_bias={use_conv_bias}, "
                             f"mlp_hidden_act={mlp_hidden_act!r}")
        kinds = []
        for letter in hybrid_override_pattern:
            if letter not in _KINDS:
                raise ValueError(f"unknown block {letter!r} in "
                                 f"hybrid_override_pattern (M, *, E)")
            kinds.append(_KINDS[letter])
        if MOE in kinds and None in (n_routed_experts, num_experts_per_tok,
                                     moe_intermediate_size):
            raise ValueError("an E block needs n_routed_experts, "
                             "num_experts_per_tok and moe_intermediate_size")
        if num_attention_heads % num_key_value_heads:
            raise ValueError(f"{num_attention_heads} query heads do not "
                             f"divide over {num_key_value_heads} K/V heads")
        if not norm_topk_prob:
            raise ValueError("norm_topk_prob false: the expert layer "
                             "renormalizes the chosen weights")
        if remat not in _MODES:
            raise ValueError(f"remat={remat!r} not in {_MODES}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.hybrid_override_pattern = hybrid_override_pattern
        self.layer_types = tuple(kinds)
        self.num_hidden_layers = len(kinds)
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.ssm_state_size = ssm_state_size
        self.n_groups = n_groups
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.n_routed_experts = n_routed_experts
        self.router_experts = router_experts or n_routed_experts
        self.experts_held_start = experts_held_start
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = (
            moe_shared_expert_intermediate_size)
        self.routed_scaling_factor = routed_scaling_factor
        self.moe_row_buffer_factor = moe_row_buffer_factor
        self.rms_norm_eps = norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.remat = remat
        self.head_chunk = head_chunk
        # what LagunaAttention and Laguna read of a configuration
        self.num_attention_heads_per_layer = (
            (num_attention_heads,) * len(kinds))
        self.rope_parameters = {}           # no kind rotates
        self.sliding_window = None
        self.gating = False
        self.qk_norm = False
        self.tie_word_embeddings = False
        self.total_ut_steps = 1

    @classmethod
    def from_dict(cls, d: dict, **over) -> "NemotronHConfig":
        """From the keys of a published config file (others are ignored).
        Where the file is a chip's share, ``n_routed_experts`` counts the
        experts held and ``num_experts_published`` the router's width."""
        names = inspect.signature(cls.__init__).parameters
        kw = {k: d[k] for k in names if k in d}
        if "num_experts_published" in d:
            kw["router_experts"] = d["num_experts_published"]
        kw.update(over)
        return cls(**kw)


class NemotronHBlock(nn.Module):
    """One norm and one mixer; -> (x, the expert layer's counters or None)."""

    def __init__(self, cfg: NemotronHConfig, layer: int):
        super().__init__()
        self.input_layernorm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        kind = cfg.layer_types[layer]
        self.mixer = {MAMBA: "mamba", FULL: "self_attn", MOE: "mlp"}[kind]
        if kind == MAMBA:
            self.mamba = Mamba2Mixer(
                cfg.hidden_size, cfg.mamba_num_heads, cfg.mamba_head_dim,
                cfg.ssm_state_size, cfg.n_groups, cfg.conv_kernel,
                cfg.chunk_size, cfg.rms_norm_eps)
        elif kind == FULL:
            self.self_attn = LagunaAttention(cfg, layer)
        else:
            self.mlp = ExpertParallelMLP(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.router_experts, capacity_factor=None,
                top_k=cfg.num_experts_per_tok, expert_type="mlp",
                activation="relu2", router_type="sigmoid",
                routed_scaling=cfg.routed_scaling_factor,
                experts_held=(cfg.experts_held_start, cfg.n_routed_experts),
                shared_hidden=cfg.moe_shared_expert_intermediate_size,
                row_buffer_factor=cfg.moe_row_buffer_factor,
                router_bias=True)

    def forward(self, p, x):
        h = self.input_layernorm(p["input_layernorm"], x)
        mixer = getattr(self, self.mixer)
        if self.mixer == "mlp":
            y, stats = mixer(p["mlp"], h, return_stats=True)
        else:
            y, stats = mixer(p[self.mixer], h), None
        return x + y, stats


class NemotronH(Laguna):
    """``Laguna``'s embedding, stack, final norm, untied head and loss over
    one-branch blocks."""

    block = NemotronHBlock
