"""Model configuration — one declarative dataclass covering every assigned
architecture family (dense / GQA / SWA / qk-norm / local:global / cross-attn /
MLA / MoE / Mamba-1 / RG-LRU / enc-dec).

A model is a stack of *blocks*.  ``layer_pattern`` names the repeating block
kinds; ``prefix_pattern`` holds non-periodic leading layers (e.g. DeepSeek's
first-k-dense).  The transformer scans over full pattern periods (stacked
params, one lowering per period) and unrolls prefix + remainder — this keeps
HLO size O(period) for 94-layer models.

Block kinds:
  attn        global causal self-attention + FFN (MoE if cfg.moe, MLA if cfg.mla)
  attn_dense  like attn but always a dense FFN (DeepSeek first-k layers)
  local       sliding-window causal self-attention + FFN
  cross       cross-attention to encoder/frontend states + dense FFN (VLM style)
  attn_cross  self-attention + cross-attention + dense FFN (enc-dec decoder)
  mamba       Mamba-1 mixer (no separate FFN)
  rglru       RG-LRU recurrent block + FFN (Griffin / RecurrentGemma)
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

BLOCK_KINDS = ("attn", "attn_dense", "local", "cross", "attn_cross", "mamba", "rglru")
ATTN_BACKENDS = ("einsum", "pallas")
ATTN_KINDS = ("attn", "attn_dense", "local", "cross", "attn_cross")
SELF_ATTN_KINDS = ("attn", "attn_dense", "local", "attn_cross")


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Routed experts: top-k of a softmax router, the k weights
    renormalised to sum to 1.  The router always spans all
    ``num_experts``; this device holds experts ``first_held ..
    first_held + num_held - 1`` (all of them by default) and computes only
    their part of each token's output, as one chip of an expert-parallel
    layer does."""

    num_experts: int
    top_k: int
    d_expert: int
    num_shared: int = 0          # shared (always-on) experts, DeepSeek style
    first_k_dense: int = 0       # leading layers that keep a dense FFN
    router_zloss: float = 1e-3
    first_held: int = 0
    num_held: int | None = None  # None: every expert is held here

    def __post_init__(self):
        if not 0 < self.top_k <= self.num_experts:
            raise ValueError("top_k must lie in 1..num_experts")
        if (self.first_held < 0 or self.held_count < 1
                or self.first_held + self.held_count > self.num_experts):
            raise ValueError(
                f"held experts {self.first_held}..+{self.held_count} do not "
                f"lie within the {self.num_experts} routed")

    @property
    def held_count(self) -> int:
        return self.num_experts if self.num_held is None else self.num_held


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_rope_dim: int = 64
    qk_nope_dim: int = 128
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0             # 0 -> ceil(d_model / 16)


@dataclasses.dataclass(frozen=True)
class RGLRUConfig:
    lru_width: int = 0           # 0 -> d_model
    conv_width: int = 4


@dataclasses.dataclass(frozen=True)
class ReCalKVRuntime:
    """Runtime shape info for a latent (compressed) KV cache.

    ``rank_k``/``rank_v`` are the uniform ranks (required for
    scan-over-layers).  When Fisher allocation varies ranks per layer
    (unrolled path), ``ranks_by_layer`` holds (rank_k, rank_v) indexed by
    global layer position ((0, 0) for attention-free layers).
    """

    rank_k: int
    rank_v: int
    group_size: int = 4
    ranks_by_layer: tuple[tuple[int, int], ...] | None = None

    def num_groups(self, num_kv_heads: int) -> int:
        s = max(1, min(self.group_size, num_kv_heads))
        return num_kv_heads // s

    def ranks_for(self, layer_idx: int | None) -> tuple[int, int]:
        if layer_idx is not None and self.ranks_by_layer is not None:
            rk, rv = self.ranks_by_layer[layer_idx]
            if rk:
                return rk, rv
        return self.rank_k, self.rank_v


_NESTED_CONFIGS = {"moe": MoEConfig, "mla": MLAConfig, "mamba": MambaConfig,
                   "rglru": RGLRUConfig}
_DTYPES_BY_NAME = {"bfloat16": jnp.bfloat16, "float16": jnp.float16,
                   "float32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    layer_pattern: tuple[str, ...] = ("attn",)
    prefix_pattern: tuple[str, ...] = ()
    sliding_window: int | None = None
    qk_norm: bool = False
    rope_theta: float = 10000.0
    rope_theta_global: float | None = None  # separate theta for global layers
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    embed_scale: bool = False    # gemma-style sqrt(d_model) embedding scale
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    mamba: MambaConfig | None = None
    rglru: RGLRUConfig | None = None
    encoder_decoder: bool = False
    num_encoder_layers: int = 0
    cross_source_len: int = 0    # frontend token count (VLM patches / audio frames)
    recalkv: ReCalKVRuntime | None = None
    attn_seq_shard: bool = False  # sequence-parallel K/V (heads % TP != 0)
    scan_layers: bool = True
    remat: bool = True
    attn_chunk: int = 512        # query-chunked attention block (memory ceiling)
    attn_backend: str = "einsum"  # "einsum" reference | "pallas" kernels
    attn_block: int = 256        # pallas kernel tile size (key/query axis)
    cache_quant_bits: int | None = None  # int8-latent self-attn ring cache
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        for k in self.layer_pattern + self.prefix_pattern:
            if k not in BLOCK_KINDS:
                raise ValueError(f"unknown block kind {k!r}")
        if self.num_heads % max(self.num_kv_heads, 1):
            raise ValueError("num_heads must be divisible by num_kv_heads")
        if self.attn_backend not in ATTN_BACKENDS:
            raise ValueError(
                f"attn_backend must be one of {ATTN_BACKENDS}, "
                f"got {self.attn_backend!r}")
        if self.cache_quant_bits is not None:
            if self.recalkv is None:
                raise ValueError("cache_quant_bits requires a recalkv "
                                 "(latent) cache")
            if self.cache_quant_bits not in (3, 4, 8):
                raise ValueError("cache_quant_bits must be 3, 4 or 8")
        n_body = self.num_layers - len(self.prefix_pattern)
        if n_body < 0:
            raise ValueError("prefix longer than the model")

    # ---- layer layout -----------------------------------------------------

    @property
    def period(self) -> int:
        return len(self.layer_pattern)

    @property
    def num_periods(self) -> int:
        return (self.num_layers - len(self.prefix_pattern)) // self.period

    @property
    def suffix_pattern(self) -> tuple[str, ...]:
        rem = (self.num_layers - len(self.prefix_pattern)) % self.period
        return self.layer_pattern[:rem]

    def expanded_layers(self) -> tuple[str, ...]:
        """Per-layer block kinds for the whole stack, in order."""
        return (
            self.prefix_pattern
            + self.layer_pattern * self.num_periods
            + self.suffix_pattern
        )

    # ---- derived dims -----------------------------------------------------

    @property
    def q_dim(self) -> int:
        if self.mla is not None:
            return self.num_heads * (self.mla.qk_nope_dim + self.mla.qk_rope_dim)
        return self.num_heads * self.d_head

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.d_head

    @property
    def mamba_d_inner(self) -> int:
        assert self.mamba is not None
        return self.mamba.expand * self.d_model

    @property
    def mamba_dt_rank(self) -> int:
        assert self.mamba is not None
        return self.mamba.dt_rank or -(-self.d_model // 16)

    @property
    def lru_width(self) -> int:
        assert self.rglru is not None
        return self.rglru.lru_width or self.d_model

    def window_for(self, kind: str) -> int | None:
        """Effective attention window for a block kind (None = unbounded)."""
        if kind == "local":
            if self.sliding_window is None:
                raise ValueError("'local' blocks need cfg.sliding_window")
            return self.sliding_window
        if kind in ("attn", "attn_dense", "attn_cross"):
            # A model whose *global* blocks also slide (h2o-danube) sets
            # sliding_window and uses kind="local" throughout instead.
            return None
        return None

    def cache_len(self, kind: str, seq_len: int) -> int:
        """KV-cache length for one block at a given max sequence length."""
        w = self.window_for(kind)
        return seq_len if w is None else min(w, seq_len)

    # ---- parameter counting (for roofline MODEL_FLOPS) ---------------------

    def param_count(self) -> int:
        """Total parameter count (embedding included once if tied)."""
        return sum(self._block_params(k) for k in self.expanded_layers()) + self._extras()

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only routed-active experts)."""
        return sum(
            self._block_params(k, active_only=True) for k in self.expanded_layers()
        ) + self._extras()

    def _extras(self) -> int:
        d, v = self.d_model, self.vocab_size
        n = v * d + d                      # embed + final norm
        if not self.tie_embeddings:
            n += d * v
        if self.encoder_decoder:
            n += self.num_encoder_layers * self._block_params("attn_dense_enc")
        return n

    def _ffn_params(self, active_only: bool) -> int:
        d = self.d_model
        if self.moe is None:
            return 3 * d * self.d_ff
        m = self.moe
        experts = min(m.top_k, m.held_count) if active_only else m.held_count
        return (
            3 * d * m.d_expert * (experts + m.num_shared)
            + d * m.num_experts  # router
        )

    def _attn_params(self) -> int:
        d = self.d_model
        if self.mla is not None:
            a = self.mla
            return (
                d * a.q_lora_rank
                + a.q_lora_rank * self.num_heads * (a.qk_nope_dim + a.qk_rope_dim)
                + d * (a.kv_lora_rank + a.qk_rope_dim)
                + a.kv_lora_rank * self.num_heads * (a.qk_nope_dim + a.v_head_dim)
                + self.num_heads * a.v_head_dim * d
            )
        return d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d

    # ---- serialization (compression artifacts) -----------------------------

    def to_dict(self) -> dict:
        """JSON-serializable form (dtype by name; tuples become lists on
        dump and are restored by :meth:`from_dict`)."""
        d = dataclasses.asdict(self)
        d["dtype"] = jnp.dtype(self.dtype).name
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ModelConfig":
        d = dict(d)
        d["dtype"] = _DTYPES_BY_NAME.get(d["dtype"]) or jnp.dtype(d["dtype"])
        for key, sub in _NESTED_CONFIGS.items():
            if d.get(key) is not None:
                d[key] = sub(**d[key])
        if d.get("recalkv") is not None:
            rt = dict(d["recalkv"])
            if rt.get("ranks_by_layer") is not None:
                rt["ranks_by_layer"] = tuple(
                    (int(rk), int(rv)) for rk, rv in rt["ranks_by_layer"])
            d["recalkv"] = ReCalKVRuntime(**rt)
        for key in ("layer_pattern", "prefix_pattern"):
            d[key] = tuple(d[key])
        return cls(**d)

    def _block_params(self, kind: str, active_only: bool = False) -> int:
        d = self.d_model
        if kind == "mamba":
            di, ds = self.mamba_d_inner, self.mamba.d_state
            dtr = self.mamba_dt_rank
            return (
                d * 2 * di + self.mamba.d_conv * di + di
                + di * (dtr + 2 * ds) + dtr * di + di * ds + di + di * d + d
            )
        if kind == "rglru":
            w = self.lru_width
            ffn = 3 * d * self.d_ff
            return 2 * d * w + self.rglru.conv_width * w + 2 * w * w + w * d + ffn + 2 * d
        if kind == "attn_dense_enc":
            return self._attn_params() + 3 * d * self.d_ff + 2 * d
        ffn = (
            3 * d * self.d_ff
            if kind in ("attn_dense", "cross", "attn_cross")
            else self._ffn_params(active_only)
        )
        attn = self._attn_params()
        if kind == "attn_cross":
            attn *= 2  # self + cross attention
        return attn + ffn + 2 * d
