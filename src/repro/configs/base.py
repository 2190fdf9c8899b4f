"""Config system: model configs, input-shape presets, run configs, registry.

Every assigned architecture registers a `ModelConfig` (exact published
numbers) plus a reduced `smoke()` variant of the same family.  Shapes are
the four assigned input-shape presets.  `resolve(arch)` backs the `--arch`
flag of every launcher/benchmark entry point.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int                 # 0 for attention-free
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 → d_model // num_heads
    # attention
    qkv_bias: bool = False
    sliding_window: int = 0        # 0 = full attention; >0 = SWA width
    rope_theta: float = 10000.0
    # mlp
    gated_mlp: bool = True         # SwiGLU vs plain GELU MLP
    act: str = "silu"
    # moe
    num_experts: int = 0
    experts_per_token: int = 0
    moe_capacity_factor: float = 1.25
    # ssm (Mamba2 / SSD)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 64
    ssm_conv_width: int = 4
    ssm_groups: int = 1
    # hybrid (Zamba2, arXiv:2411.15242): a Mamba2 mixer at every layer; at
    # each layer of hybrid_layer_ids one of num_mem_blocks weight-shared
    # attention+MLP blocks (in turn) reads concat(h, token embeddings), and
    # its output, through that application's own rank-adapter_rank MLP
    # adapter and d x d projection, joins the Mamba layer's input
    hybrid_layer_ids: tuple = ()
    num_mem_blocks: int = 0
    adapter_rank: int = 0
    # encoder-decoder (Whisper): frontend stubbed to frame embeddings
    encoder_layers: int = 0
    encoder_seq: int = 0
    cross_attention: bool = False
    # vlm: stub patch-embedding prefix of this many tokens
    vision_tokens: int = 0
    # numerics
    dtype: str = "bfloat16"
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    def hd(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def subquadratic(self) -> bool:
        """Eligible for long_500k (SSM / hybrid / sliding-window)."""
        return self.family in ("ssm", "hybrid") or self.sliding_window > 0

    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    def ssm_heads(self) -> int:
        return self.d_inner() // self.ssm_head_dim

    # -- parameter count (analytic, for roofline MODEL_FLOPS) -------------
    def param_count(self, active_only: bool = False) -> int:
        d, f, L = self.d_model, self.d_ff, self.num_layers
        hd, H, K = self.hd(), self.num_heads, self.num_kv_heads
        total = self.vocab_size * d                       # embed
        if not self.tie_embeddings:
            total += self.vocab_size * d                  # lm head
        per_attn = d * H * hd + 2 * d * K * hd + H * hd * d
        if self.qkv_bias:
            per_attn += (H + 2 * K) * hd
        per_mlp = (3 if self.gated_mlp else 2) * d * f
        if self.family == "moe":
            E = self.experts_per_token if active_only else self.num_experts
            per_mlp = (3 if self.gated_mlp else 2) * d * f * E + d * self.num_experts
        per_norms = 2 * d
        if self.family == "ssm":
            di, S, Hs = self.d_inner(), self.ssm_state, self.ssm_heads()
            G = self.ssm_groups
            per_layer = (d * (2 * di + 2 * G * S + Hs)    # in_proj
                         + self.ssm_conv_width * (di + 2 * G * S)
                         + 3 * Hs + di                    # A, D, dt_bias, norm
                         + di * d + d)                    # out_proj + ln
            total += L * per_layer
        elif self.family == "hybrid":
            di, S, Hs = self.d_inner(), self.ssm_state, self.ssm_heads()
            G = self.ssm_groups
            per_m = (d * (2 * di + 2 * G * S + Hs)
                     + (self.ssm_conv_width + 1) * (di + 2 * G * S)
                     + 3 * Hs + di + di * d + d)
            total += L * per_m
            # the shared blocks read concat(h, embeddings): 2d wide in
            n_app, r = len(self.hybrid_layer_ids), self.adapter_rank
            shared = (3 * 2 * d * H * hd + H * hd * d + 3 * d + 3 * d * f)
            total += self.num_mem_blocks * shared
            total += n_app * (d * r + r * 2 * f + d * d)  # per application
        else:
            total += L * (per_attn + per_mlp + per_norms)
            if self.encoder_layers:
                total += self.encoder_layers * (per_attn + per_mlp + per_norms)
                if self.cross_attention:                  # decoder cross-attn
                    total += L * (per_attn + d)
        total += d                                        # final norm
        return int(total)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """One training/serving cell.

    The communication fields (``gradsync``/``gradsync_buckets``/
    ``fsdp_prefetch``) are the legacy knobs behind
    ``repro.comm.CommConfig.from_run`` — the valid ``gradsync`` values
    are whatever the repro.comm registry has registered (this docstring
    is completed from the registry at import time, so new registrations
    are self-documenting):

    gradsync strategies: {gradsync_strategies}

    ``plan`` names a dry-run sharding PLAN ("default" | "tp0" — which
    axes join the batch product, launch/dryrun.py), NOT a gradsync
    strategy; the two used to share the ``gradsync`` field, which
    bypassed the registry validation below.
    """
    model: ModelConfig
    shape: ShapeConfig
    fsdp: bool = False             # shard params over the data axis too
    remat: str = "none"            # none | full | dots
    # valid values derive from the repro.comm registry — see the class
    # docstring (filled from strategies_for("grad_sync") at import) —
    # and are VALIDATED at construction (__post_init__): an unknown
    # strategy fails here, not three layers down inside a step builder
    gradsync: str = "native"
    # dry-run sharding plan name (launch/dryrun.py); free-form tag, the
    # dryrun layer owns its meaning
    plan: str = "default"
    # gradient-sync bucket count; 0 = cost-model auto (§5 latency/bandwidth
    # crossover, core.costmodel.optimal_num_buckets)
    gradsync_buckets: int = 0
    # lane_zero3 per-layer weight-gather pipeline blocks:
    #   0  = cost-model auto (core.costmodel.optimal_prefetch_blocks)
    #   >0 = that many AG(lane)→AG(node) blocks, one-layer prefetch
    #   -1 = BLOCKING gather (no prefetch; the negative control — layer i's
    #        compute depends on its own all-gather)
    fsdp_prefetch: int = 0
    # lane_zero3 backward re-gather: re-run each layer's weight gather in
    # the backward under remat so backward residuals stay 1/p + 1 layer
    # instead of L·D per chip (models/blockstack.ShardedStack.regather)
    fsdp_regather: bool = False
    scan_layers: bool = True
    microbatch: int = 0            # 0 = no grad accumulation
    # microbatch gradient-accumulation precision (honored by the GSPMD
    # dryrun step AND the lane step builders): "float32" is parity-exact,
    # "bfloat16" halves the accumulator's HBM residency
    accum_dtype: str = "float32"
    # tensor parallelism over the mesh's "model" axis: MLP activation
    # collectives (allgather fwd+bwd) run through (collective, strategy)
    # cells of a model-axis LaneComm (models/layers.mlp_tp); 1 = off.
    # The mesh's "model" axis size must equal this degree.
    model_parallel: int = 1
    # expert parallelism for MoE families: token routing dispatch/combine
    # as the paper's decomposed alltoall over the BATCH axes ("moe_route"
    # cells) — every chip owns E/p experts; under lane_zero3 the expert
    # weights live in a never-gathered (L, E/p, ...) local master
    expert_parallel: bool = False
    # capacity-dim software pipelining depth of the routing alltoall
    # (moe_block_ep): >1 splits the C dim so block j+1's dispatch
    # alltoall overlaps block j's expert FFN; 1 = sequential
    ep_blocks: int = 1
    # serving
    decode_seq_shard: bool = True  # shard KV cache seq dim over model axis

    def __post_init__(self):
        if self.accum_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"accum_dtype must be 'float32' or 'bfloat16', got "
                f"{self.accum_dtype!r}")
        # registry-derived validation: dryrun used to smuggle plan names
        # through this field, silently skipping the check every other
        # consumer relied on.  Union of the grad_sync and train_step
        # tables (a strategy may register only a step builder); "auto"
        # is meta — it dispatches per call, so it has no grad_sync cell
        from repro.comm import strategies_for
        valid = dict.fromkeys((*strategies_for("grad_sync"),
                               *strategies_for("train_step"), "auto"))
        if self.gradsync not in valid:
            raise ValueError(
                f"unknown gradsync strategy {self.gradsync!r}; registered "
                f"strategies: {tuple(valid)} (plan names belong in "
                f"RunConfig.plan)")
        if self.model_parallel < 1:
            raise ValueError(
                f"model_parallel must be >= 1, got {self.model_parallel}")
        if self.ep_blocks < 1:
            raise ValueError(
                f"ep_blocks must be >= 1, got {self.ep_blocks}")
        if self.model_parallel > 1 \
                and self.gradsync in ("lane_zero1", "lane_quorum"):
            # zero1's bucket-major flat shard has no model-axis assembly
            # mask, and the quorum rescale math assumes batch-only axes
            raise ValueError(
                f"model_parallel > 1 is not supported with gradsync="
                f"{self.gradsync!r} (use native/lane/lane_zero3)")
        if self.expert_parallel:
            if getattr(self.model, "num_experts", 0) < 1:
                raise ValueError(
                    f"expert_parallel needs a MoE model (family "
                    f"{self.model.family!r} has no experts)")
            if self.gradsync == "lane_quorum":
                # a masked pod still sits on the routing alltoall's wire;
                # degraded-quorum EP routing is future work
                raise ValueError(
                    "expert_parallel is not supported with "
                    "gradsync='lane_quorum'")


def _fill_rundoc() -> None:
    """Complete RunConfig's docstring from the live registry (satellite:
    valid-strategy lists are DERIVED, never hard-coded)."""
    if not RunConfig.__doc__:        # stripped under python -OO
        return
    from repro.comm import strategies_for
    names = " | ".join((*strategies_for("grad_sync"), "auto"))
    RunConfig.__doc__ = RunConfig.__doc__.format(gradsync_strategies=names)


_fill_rundoc()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
_REGISTRY: dict[str, Callable[[], ModelConfig]] = {}
_SMOKE: dict[str, Callable[[], ModelConfig]] = {}


def register(arch_id: str, full: Callable[[], ModelConfig],
             smoke: Callable[[], ModelConfig]) -> None:
    _REGISTRY[arch_id] = full
    _SMOKE[arch_id] = smoke


def resolve(arch_id: str, smoke: bool = False) -> ModelConfig:
    import repro.configs as _  # ensure arch modules imported  # noqa: F401
    table = _SMOKE if smoke else _REGISTRY
    if arch_id not in table:
        raise KeyError(f"unknown arch {arch_id!r}; have {sorted(table)}")
    return table[arch_id]()


def all_archs() -> list[str]:
    import repro.configs as _  # noqa: F401
    return sorted(_REGISTRY)


def cells(arch_id: str) -> list[str]:
    """The shape presets this arch runs (long_500k only if sub-quadratic)."""
    cfg = resolve(arch_id)
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.subquadratic:
        out.append("long_500k")
    return out
