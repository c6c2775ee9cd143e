"""Config system for the FLUDE reproduction framework.

Every assigned architecture gets a ``ModelConfig``; the four assigned input
shapes are ``InputShape`` entries in ``INPUT_SHAPES``.  Configs are plain
frozen dataclasses so they hash/compare and can be embedded in jit static
args.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple


# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    num_shared_experts: int = 0
    expert_d_ff: Optional[int] = None      # d_ff of each routed expert
    shared_d_ff: Optional[int] = None      # d_ff of the shared expert(s)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    first_k_dense_replace: int = 0         # leading layers with a dense MLP
    experts_held: Optional[int] = None
    # ^ experts one chip holds under expert parallelism (a share of
    #   num_experts); None = all.  The router still scores every expert.


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2).  ``q_lora_rank=None``
    projects queries with one plain ``q_proj`` (DeepSeek-V2-Lite)."""
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (arXiv 2309.00071), DeepSeek-V2's form."""
    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class LoRAConfig:
    """Low-rank adapters: a projection x W becomes x W + (alpha / rank)
    x a b, on MLA's q, kv_a, kv_b and o projections."""
    rank: int = 16
    alpha: float = 32.0

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) block config."""
    d_state: int = 64
    expand: int = 2
    head_dim: int = 64
    conv_kernel: int = 4
    chunk_size: int = 256
    n_groups: int = 1          # B/C groups (like GQA for SSM)


@dataclass(frozen=True)
class RWKVConfig:
    head_dim: int = 64
    decay_lora_rank: int = 64
    gate_lora_rank: int = 64
    token_shift: bool = True


@dataclass(frozen=True)
class HybridConfig:
    """zamba2-style hybrid: Mamba2 backbone + shared attention block."""
    attn_every: int = 6        # apply the shared attention block every N layers
    shared_attn_blocks: int = 1


@dataclass(frozen=True)
class EncDecConfig:
    """whisper-style encoder-decoder."""
    num_encoder_layers: int = 32
    num_decoder_layers: int = 32
    max_target_len: int = 448


@dataclass(frozen=True)
class VisionStubConfig:
    """VLM frontend stub: precomputed patch embeddings are model inputs."""
    num_image_tokens: int = 1024   # patch tokens prepended to the sequence
    patch_embed_dim: int = 1024    # CLIP-style embed dim before projector


# ---------------------------------------------------------------------------
# ModelConfig
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                     # dense | moe | ssm | hybrid | vlm | audio
    source: str                        # citation (arXiv id / hf model card)
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None     # default: d_model // num_heads
    # attention flavour
    attention: str = "gqa"             # gqa | mla | none (attention-free)
    qkv_bias: bool = False
    sliding_window: Optional[int] = None
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnConfig] = None
    norm_eps: float = 1e-5
    # mlp flavour
    mlp_act: str = "silu_glu"          # silu_glu | gelu | relu2
    norm: str = "rmsnorm"              # rmsnorm | layernorm
    tie_embeddings: bool = False
    # family-specific blocks
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    rwkv: Optional[RWKVConfig] = None
    hybrid: Optional[HybridConfig] = None
    encdec: Optional[EncDecConfig] = None
    vision: Optional[VisionStubConfig] = None
    # numerics
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    # scan/remat
    scan_layers: bool = True
    remat: bool = True

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // self.num_heads

    def reduced(self, **overrides) -> "ModelConfig":
        """A smoke-test-sized variant of the same family (<=2 layers etc.)."""
        changes = dict(
            name=self.name + "-reduced",
            num_layers=2,
            d_model=min(self.d_model, 256),
            num_heads=min(self.num_heads, 4),
            num_kv_heads=min(self.num_kv_heads, 2),
            d_ff=min(self.d_ff, 512),
            vocab_size=min(self.vocab_size, 512),
            head_dim=64,
            param_dtype="float32",
            compute_dtype="float32",
        )
        if self.moe is not None:
            changes["moe"] = dataclasses.replace(
                self.moe,
                num_experts=min(self.moe.num_experts, 4),
                top_k=min(self.moe.top_k, 2),
                num_shared_experts=min(self.moe.num_shared_experts, 1),
                expert_d_ff=min(self.moe.expert_d_ff or self.d_ff, 256),
                shared_d_ff=min(self.moe.shared_d_ff or self.d_ff, 256),
            )
        if self.mla is not None:
            changes["mla"] = MLAConfig(
                q_lora_rank=None if self.mla.q_lora_rank is None else 64,
                kv_lora_rank=32,
                qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32)
            changes["head_dim"] = None
        if self.ssm is not None:
            changes["ssm"] = dataclasses.replace(
                self.ssm, d_state=16, head_dim=32, chunk_size=32)
        if self.rwkv is not None:
            changes["rwkv"] = dataclasses.replace(
                self.rwkv, head_dim=32, decay_lora_rank=16, gate_lora_rank=16)
        if self.hybrid is not None:
            changes["hybrid"] = dataclasses.replace(self.hybrid, attn_every=2)
            changes["num_layers"] = 4
        if self.encdec is not None:
            changes["encdec"] = dataclasses.replace(
                self.encdec, num_encoder_layers=2, num_decoder_layers=2,
                max_target_len=16)
        if self.vision is not None:
            changes["vision"] = dataclasses.replace(
                self.vision, num_image_tokens=8, patch_embed_dim=64)
        if self.sliding_window is not None:
            changes["sliding_window"] = 16
        changes.update(overrides)
        return dataclasses.replace(self, **changes)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k":    InputShape("train_4k",    4_096,   256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768,  32,  "prefill"),
    "decode_32k":  InputShape("decode_32k",  32_768,  128, "decode"),
    "long_500k":   InputShape("long_500k",   524_288, 1,   "decode"),
}


# ---------------------------------------------------------------------------
# Train / FL configs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    optimizer: str = "adamw"           # sgd | momentum | adam | adamw
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    moment_dtype: str = "float32"      # Adam m/v dtype (bf16 for >=200B)
    accum_dtype: str = "float32"       # microbatch grad accumulator dtype
    microbatch_size: Optional[int] = None   # per-silo microbatch for grad accum
    warmup_steps: int = 100
    total_steps: int = 1000
    seed: int = 0


@dataclass(frozen=True)
class FLConfig:
    """FLUDE hyper-parameters (paper §5.2 defaults)."""
    num_clients: int = 256
    clients_per_round: int = 32
    local_steps: int = 4
    model: str = "mlp"
    # ^ registered FL model (repro.fl.models) the clients train: "mlp"
    #   (the classifier of repro.fl.classifier, every leaf trained) or
    #   "deepseek-v2-lite" (LoRA adapters over a frozen MLA + MoE trunk,
    #   repro.configs.deepseek_v2_lite).  Only the trainable leaves are
    #   packed, aggregated and cached.
    # selection (Alg. 1)
    selection_mode: str = "mean"       # mean | thompson (beyond-paper)
    epsilon_init: float = 0.9          # exploration factor
    epsilon_decay: float = 0.98
    epsilon_min: float = 0.2
    sigma: float = 0.5                 # frequency penalty exponent
    # dependability prior (Eq. 1)
    beta_alpha0: float = 2.0
    beta_beta0: float = 2.0
    # staleness distribution (Eq. 4)
    lam: float = 1.0                   # λ — staleness coefficient
    mu: float = 0.5                    # μ — comm-cost coefficient
    w_init: float = 3.0                # initial staleness threshold
    w_min: float = 1.0
    w_max: float = 50.0
    # round process (Alg. 2)
    comm_budget: float = float("inf")  # B_max, in model-transmission units
    round_deadline: float = 600.0      # T, seconds (simulator wall clock)
    # caching (C3)
    cache_enabled: bool = True
    base_cache_interval: float = 60.0  # seconds between cache writes
    distribution_mode: str = "adaptive"  # adaptive | full | least
    # server aggregation (§4.3 hot path): packed whole-model kernel
    staleness_discount: float = 1.0    # per-round decay of stale-base weights
    agg_impl: str = "xla"              # xla | pallas | pallas_interpret
    agg_block_c: int = 8               # client-axis tile of the Pallas kernel
    agg_block_d: int = 2048            # packed-param-axis tile
    agg_rule: str = "mean"
    # ^ registered robust-aggregation rule (repro.core.agg_rules):
    #   "mean" (the historical weighted mean — bit-identical default),
    #   "geometric_median" (smoothed Weiszfeld / RFA), "trimmed_mean"
    #   (coordinate-wise), "trust" (per-client trust state learned on
    #   device from update-deviation norms).  Orthogonal to agg_impl.
    agg_rule_params: Tuple[Tuple[str, Any], ...] = ()
    # ^ hashable ((key, value), ...) pairs forwarded to the rule
    #   constructor (e.g. (("iters", 8),) for geometric_median)
    adversary: Optional[str] = None
    # ^ registered attack model (repro.fleet.adversary): a deterministic
    #   malicious_frac slice of the fleet misbehaves — "label_flip"
    #   corrupts local labels, "sign_flip"/"grad_scale" transform the
    #   malicious uploads inside the jitted server step.  None = benign.
    adversary_params: Tuple[Tuple[str, Any], ...] = ()
    # ^ hashable ((key, value), ...) pairs forwarded to the adversary
    #   constructor (e.g. (("malicious_frac", 0.2),))
    # mesh & memory (cross-device round path)
    mesh_shape: Optional[Tuple[int, ...]] = None
    # ^ (k,) shards the fleet k-ways over the ("clients",) mesh axis
    #   (stacked client pytree, packed (C, D) buffer, (N,) scalar state);
    #   None = single-device round path (bit-identical to the golden runs)
    donate_buffers: bool = False
    # ^ donate dead round inputs on the jitted trainer / server_round_step
    #   so XLA aliases them into the outputs (steady-state rounds allocate
    #   nothing new); donated host-side handles are invalidated
    cohort_size: Optional[int] = None
    # ^ static X: compact selected-cohort round path.  None = full scan
    #   (trainer/cut/aggregation run over all N clients, masked).  An int
    #   makes the engine gather the selected clients' data, caches, draw
    #   and plan arrays into dense (X, ...) blocks on device, run local
    #   training, the round cut and the packed aggregation over X rows,
    #   and scatter the results back into the (N,)-sized fleet state —
    #   round cost tracks the cohort instead of the fleet while fleet
    #   state stays the only N-proportional memory.  Trajectories are
    #   bit-identical to the full scan on a single device; under a client
    #   mesh the integer trajectory (received/selected/wall clock) is
    #   exact and accuracies agree to float tolerance (cohort rows
    #   regroup across shards, so the psum reassociates).  Every plan's
    #   selected count must fit in X (the engine rejects policies whose
    #   ``selection_bound()`` exceeds it up front, and flags runtime
    #   overflow — under ``pipeline_depth`` > 1 the overflow check is
    #   read back with the deferred ledger, i.e. up to depth-1 rounds
    #   late).  Requires a device dynamics process (not bernoulli_host)
    #   and, under a mesh, ``cohort_size % mesh_shape[0] == 0``.
    cache_offload: Optional[str] = None
    # ^ C3 cache residency (requires cohort_size).  None keeps today's
    #   device-resident (N, D) cache pytree.  "host" keeps only the (N,)
    #   cache *metadata* (progress, round stamp — what planning reads)
    #   on device plus the current cohort's (X, D) slot block; written
    #   slots stream back to a sparse host store with async dispatch /
    #   double buffering (repro.core.cache_store) and the next cohort's
    #   slots are prefetched as soon as its selection mask is known —
    #   device cache memory scales with X, trajectories stay
    #   bit-identical to the resident path.  "discard" additionally
    #   drops rows unselected for more than cache_staleness_bound
    #   rounds (device metadata expiry + host-store prune) — a legal
    #   memory/accuracy knob, since the paper's cache is best-effort.
    cache_staleness_bound: int = 32
    # ^ "discard" mode: rounds a cache row survives without a rewrite
    #   before it is dropped (host row pruned, device metadata reset
    #   before planning).  Ignored by the other offload modes.
    # fleet dynamics (repro.fleet): availability process + scenario params
    dynamics: str = "bernoulli_host"
    # ^ registered process name.  "bernoulli_host" is the seed simulator's
    #   host-RNG path (bit-identical golden trajectories); every other
    #   process draws on device under the client mesh — no per-round
    #   host→device hand-off.  Scenario presets (repro.fleet.scenarios)
    #   set this plus dynamics_params in one go.
    dynamics_params: Tuple[Tuple[str, Any], ...] = ()
    # ^ hashable ((key, value), ...) pairs forwarded to the process
    #   constructor (e.g. (("mean_on", 5.0),) for markov churn)
    pipeline_depth: int = 1
    # ^ rounds in flight on the device round path (device dynamics only).
    #   1 = the classic loop: the host resolves each round's bookkeeping
    #   (duration, received counts, eval) before planning the next round.
    #   depth d keeps up to d-1 rounds of bookkeeping pending, so round
    #   k+1's fused trainer + server step are dispatched while round k
    #   still executes — trajectories are bit-identical at every depth
    #   (the round close runs jitted on device; History rows are resolved
    #   from device scalars in arrival order).  ``time_budget`` runs
    #   resolve every round regardless (the budget check needs cum_time).
    telemetry: Optional[str] = None
    # ^ default device-metrics level for engine runs (repro.obs).  None
    #   compiles telemetry out entirely — the round path is bit- and
    #   dispatch-count-identical to an uninstrumented engine.  "basic"
    #   fuses the cheap participation/loss/cache counters into one extra
    #   jitted dispatch per round; "full" adds update/residual norms,
    #   trust quantiles and the staleness histogram.  Either way metric
    #   values ride the pipelined round ledger — zero added per-round
    #   host syncs.  "spans" turns on host span tracing alone (the
    #   ``fl.*`` annotations of a ``jax.profiler`` trace) and compiles
    #   no metrics dispatch.  ``FleetEngine.run(telemetry=...)``
    #   overrides per run (a level string or a ``repro.obs.Telemetry``
    #   session with sinks/tracing attached).
    debug_checks: bool = False
    # ^ runtime-sanitizer mode (repro.analysis.runtime): after each
    #   server step a checkify guard validates the new global model and
    #   per-client losses are finite and the cohort index is in bounds,
    #   and a recompilation detector asserts at run end that none of the
    #   engine's memoized jitted dispatches re-traced across runs.  Adds
    #   one host sync per round — a debugging tool, never a production
    #   mode; the static auditor (repro.analysis.audit) verifies the
    #   same contracts with zero runtime cost.

    def __post_init__(self):
        if self.telemetry not in (None, "spans", "basic", "full"):
            raise ValueError(
                f"FLConfig.telemetry must be None, 'spans', 'basic' or "
                f"'full', got {self.telemetry!r}")
        if self.agg_impl not in ("xla", "pallas", "pallas_interpret"):
            raise ValueError(
                f"FLConfig.agg_impl must be one of 'xla', 'pallas', "
                f"'pallas_interpret', got {self.agg_impl!r}")
        # registry lookups fail fast at construction instead of deep
        # inside the jitted round step; imported lazily — the registries
        # live above configs in the import graph
        if self.agg_rule != "mean":
            from repro.core.agg_rules import available_agg_rules
            if self.agg_rule not in available_agg_rules():
                raise ValueError(
                    f"FLConfig.agg_rule must be a registered agg rule "
                    f"({', '.join(available_agg_rules())}), got "
                    f"{self.agg_rule!r}")
        if self.adversary is not None:
            from repro.fleet.adversary import available_adversaries
            if self.adversary not in available_adversaries():
                raise ValueError(
                    f"FLConfig.adversary must be a registered adversary "
                    f"({', '.join(available_adversaries())}) or None, "
                    f"got {self.adversary!r}")
        from repro.fl.models import available_models
        if self.model not in available_models():
            raise ValueError(
                f"FLConfig.model must be a registered FL model "
                f"({', '.join(available_models())}), got {self.model!r}")
        from repro.fleet.api import available_dynamics
        if self.dynamics not in available_dynamics():
            raise ValueError(
                f"FLConfig.dynamics must be a registered dynamics "
                f"process ({', '.join(available_dynamics())}), got "
                f"{self.dynamics!r}")
        if self.cache_offload not in (None, "host", "discard"):
            raise ValueError(
                f"FLConfig.cache_offload must be None, 'host' or "
                f"'discard', got {self.cache_offload!r}")
        if self.cache_offload is not None and self.cohort_size is None:
            raise ValueError(
                f"FLConfig.cache_offload={self.cache_offload!r} requires "
                f"cohort_size — only the compact cohort path knows which "
                f"(X, D) cache slots a round touches; set cohort_size or "
                f"keep cache_offload=None for the resident pytree")
        b = self.cache_staleness_bound
        if not isinstance(b, int) or isinstance(b, bool) or b < 1:
            raise ValueError(
                f"FLConfig.cache_staleness_bound must be a positive int, "
                f"got {b!r}")
        x = self.cohort_size
        if x is None:
            return
        if not isinstance(x, int) or isinstance(x, bool) or x < 1:
            raise ValueError(
                f"FLConfig.cohort_size must be a positive int or None, "
                f"got {x!r}")
        if x > self.num_clients:
            raise ValueError(
                f"FLConfig.cohort_size ({x}) exceeds num_clients "
                f"({self.num_clients}) — a cohort cannot be larger than "
                f"the fleet; use cohort_size=None for the full scan")
        shape = self.mesh_shape
        if shape is not None and len(shape) >= 1 and shape[0] > 1 \
                and x % shape[0] != 0:
            raise ValueError(
                f"FLConfig.cohort_size ({x}) must be divisible by the "
                f"client mesh size ({shape[0]}) — the gathered (X, ...) "
                f"cohort block shards over the ('clients',) axis and "
                f"shard_map needs an even split")


@dataclass(frozen=True)
class MeshConfig:
    multi_pod: bool = False
    data_axis: int = 16
    model_axis: int = 16
    pods: int = 2

    @property
    def shape(self) -> Tuple[int, ...]:
        if self.multi_pod:
            return (self.pods, self.data_axis, self.model_axis)
        return (self.data_axis, self.model_axis)

    @property
    def axis_names(self) -> Tuple[str, ...]:
        if self.multi_pod:
            return ("pod", "data", "model")
        return ("data", "model")
