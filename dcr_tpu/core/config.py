"""Config system: typed dataclasses + CLI overrides + JSON round-trip.

Replaces the reference's per-script argparse blobs (diff_train.py:54-280,
diff_retrieval.py:124-182, diff_inference.py:204-219) and its
filesystem-as-config-database pattern (diff_train.py:745-760 encodes the config
into the output dir name; diff_inference.py:47-71 parses it back out of path
substrings). Here every run serializes its full config to
``<output_dir>/config.json`` so downstream stages read it directly, while
:func:`run_name` still produces a compatible human-readable directory name.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from dataclasses import dataclass, field, fields, is_dataclass
from pathlib import Path
from typing import Any, Optional, Sequence, Type, TypeVar, get_args, get_origin

T = TypeVar("T")

# ---------------------------------------------------------------------------
# Enumerated capability regimes (SURVEY.md §2.1 capability checklist).
# ---------------------------------------------------------------------------

DUPLICATION_REGIMES = ("nodup", "dup_both", "dup_image")
# Caption-conditioning regimes (reference diff_train.py:90-96; datasets.py:128-142).
CONDITIONING_REGIMES = (
    "nolevel",
    "classlevel",
    "instancelevel_blip",
    "instancelevel_random",
    "instancelevel_ogcap",
)
# Train-time caption mitigations (reference diff_train.py:257-262, datasets.py:100-125).
TRAIN_MITIGATIONS = ("none", "allcaps", "randrepl", "randwordadd", "wordrepeat")
# Inference-time prompt augmentations (reference diff_inference.py:14-30).
INFERENCE_AUGS = ("none", "rand_numb_add", "rand_word_add", "rand_word_repeat")


@dataclass
class MeshConfig:
    """Device-mesh shape. Axes with size 1 are still named so sharding rules are
    uniform from 1 chip to a multi-host pod (SURVEY.md §5.8). `seq` is the
    sequence/context-parallel axis consumed by ops.ring_attention."""

    data: int = -1  # -1: all remaining devices
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1

    def axis_sizes(self, n_devices: int) -> tuple[int, int, int, int]:
        d, f, t, s = self.data, self.fsdp, self.tensor, self.seq
        known = max(1, f) * max(1, t) * max(1, s)
        if d == -1:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by fsdp*tensor*seq={known}")
            d = n_devices // known
        if d * f * t * s != n_devices:
            raise ValueError(f"mesh {d}x{f}x{t}x{s} != {n_devices} devices")
        return d, f, t, s


@dataclass
class LongcatFlashConfig:
    """Sizes of the LongCat-Flash text tower (models/longcat_flash.py), under
    the keys of the published config.json (meituan-longcat/LongCat-Flash-Chat);
    the defaults are the published values. The vocabulary (or the slice of it
    held here) and the sequence length are ModelConfig.text_vocab_size and
    text_max_length, as for every tower."""

    hidden_size: int = 6144
    ffn_hidden_size: int = 12288          # the two dense SwiGLU FFNs of a layer
    expert_ffn_hidden_size: int = 2048    # a routed expert's SwiGLU
    num_layers: int = 28                  # double layers: 2 MLA + 2 FFN + 1 MoE
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512           # all of them: the router's routed outputs
    zero_expert_num: int = 256            # identity experts: the router's other outputs
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    # The routed experts THIS device holds, a contiguous range of the
    # n_routed_experts (the chip's share under expert parallelism; the layer
    # still routes over every output and adds nothing for experts held
    # elsewhere). -1 holds all from held_experts_first on.
    held_experts_first: int = 0
    held_experts_count: int = -1

    @staticmethod
    def tiny() -> "LongcatFlashConfig":
        """CPU-test size: every mechanism present (8 routed + 4 zero-compute
        experts, top 3, 2 double layers), nothing wide."""
        return LongcatFlashConfig(
            hidden_size=64, ffn_hidden_size=128, expert_ffn_hidden_size=32,
            num_layers=2, num_attention_heads=4, q_lora_rank=32,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=8, zero_expert_num=4, moe_topk=3)

    def held_range(self) -> tuple[int, int]:
        return _held_range(self)

    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    def experts_per_token(self) -> int:
        return self.moe_topk

    def layer_counts(self) -> tuple[int, int]:
        """(layers, those of them with an expert layer): every double layer
        has one."""
        return self.num_layers, self.num_layers

    def validate(self, name: str) -> None:
        _even_rope(self, name)


def _held_range(block) -> tuple[int, int]:
    count = (block.n_routed_experts - block.held_experts_first
             if block.held_experts_count < 0 else block.held_experts_count)
    return block.held_experts_first, count


def _even_rope(block, name: str) -> None:
    if block.qk_rope_head_dim % 2:
        raise ValueError(
            f"model.{name}.qk_rope_head_dim must be even (rotary pairs)")


@dataclass
class OpenPanguUltraMoEConfig:
    """Sizes of the openPangu-Ultra-MoE text tower
    (models/openpangu_ultra_moe.py), under the keys of the published
    config.json (FreedomIntelligence/openPangu-Ultra-MoE-718B); the defaults
    are the published values. The vocabulary (or the slice of it held here)
    and the sequence length are ModelConfig.text_vocab_size and
    text_max_length, as for every tower."""

    hidden_size: int = 7680
    intermediate_size: int = 18432        # the dense SwiGLU of a leading layer
    moe_intermediate_size: int = 2048     # a routed or shared expert's SwiGLU
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3        # leading layers whose FFN is dense
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256           # all of them: the router's outputs
    n_shared_experts: int = 1             # every token passes them
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True           # the chosen weights sum to one, then scale
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    rope_theta: float = 2.56e7
    # The routed experts THIS device holds: as LongcatFlashConfig's.
    held_experts_first: int = 0
    held_experts_count: int = -1

    @staticmethod
    def tiny() -> "OpenPanguUltraMoEConfig":
        """CPU-test size: every mechanism present (one dense layer, two
        expert layers of 8 routed experts and a shared one, top 3), nothing
        wide."""
        return OpenPanguUltraMoEConfig(
            hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, first_k_dense_replace=1,
            num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=8, num_experts_per_tok=3)

    def held_range(self) -> tuple[int, int]:
        return _held_range(self)

    def router_outputs(self) -> int:
        return self.n_routed_experts

    def experts_per_token(self) -> int:
        return self.num_experts_per_tok

    def layer_counts(self) -> tuple[int, int]:
        """(layers, those of them with an expert layer): all but the leading
        dense ones."""
        return self.num_hidden_layers, max(
            0, self.num_hidden_layers - self.first_k_dense_replace)

    def validate(self, name: str) -> None:
        _even_rope(self, name)


@dataclass
class SolarOpen2Config:
    """Sizes of the Solar Open 2 text tower (models/solar_open2.py), under the
    keys of the published config.json (upstage/Solar-Open2-250B), the keys of
    its `linear_attn_config` under a `linear_attn_` prefix; the defaults are
    the published values. Only keys the tower reads are here: the published
    file's others (`use_rope` false, `kda_use_full_proj` false,
    `first_k_dense_replace` 0, `linear_attn_config`'s `num_kv_heads` null,
    `intermediate_size`, which no layer reads) describe what the tower does
    anyway. The vocabulary (or the slice of it
    held here) and the sequence length are ModelConfig.text_vocab_size and
    text_max_length, as for every tower."""

    hidden_size: int = 4096
    num_hidden_layers: int = 48
    # the layers whose mixer is gated GQA softmax attention without position
    # encoding; every other layer's is the gated delta rule (KDA)
    gqa_layers: tuple[int, ...] = tuple(range(0, 48, 4))
    num_attention_heads: int = 64         # GQA: query heads
    num_key_value_heads: int = 8          # GQA: key/value heads
    head_dim: int = 128
    use_gqa_gate: bool = True             # GQA: an elementwise sigmoid gate on its output
    linear_attn_num_heads: int = 64       # KDA: heads
    linear_attn_head_dim: int = 128       # KDA: a head's key and value width
    linear_attn_short_conv_kernel_size: int = 4
    kda_allow_neg_eigval: bool = True     # KDA: beta in (0, 2), not (0, 1)
    moe_intermediate_size: int = 1280     # a routed or shared expert's SwiGLU
    n_routed_experts: int = 320           # all of them: the router's outputs
    n_shared_experts: int = 1             # every token passes them
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True           # the chosen weights sum to one, then scale
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    # The routed experts THIS device holds: as LongcatFlashConfig's.
    held_experts_first: int = 0
    held_experts_count: int = -1

    @staticmethod
    def tiny() -> "SolarOpen2Config":
        """CPU-test size: one whole period (a GQA layer, then three KDA
        layers) of 8 routed experts and a shared one, top 3, nothing wide."""
        return SolarOpen2Config(
            hidden_size=64, num_hidden_layers=4, gqa_layers=(0,),
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            linear_attn_num_heads=4, linear_attn_head_dim=16,
            moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=3)

    def held_range(self) -> tuple[int, int]:
        return _held_range(self)

    def router_outputs(self) -> int:
        return self.n_routed_experts

    def experts_per_token(self) -> int:
        return self.num_experts_per_tok

    def layer_counts(self) -> tuple[int, int]:
        """(layers, those of them with an expert layer): every one."""
        return self.num_hidden_layers, self.num_hidden_layers

    def validate(self, name: str) -> None:
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"model.{name}.num_key_value_heads must divide "
                             "num_attention_heads")


@dataclass(frozen=True)
class TextTower:
    """One row of TEXT_TOWERS: what every reader of `ModelConfig.text_tower`
    needs to know of an architecture."""

    module: str                 # "<module>:<class>", built by models/text_tower.py
    block: Optional[str]        # the ModelConfig field that holds its sizes
    held_dtype: str             # what its frozen leaves are HELD in
    # whether train_text_encoder may be set: a tower held in bfloat16 is 2
    # bytes a parameter frozen and 16 trained
    trainable: bool


#: name -> row. CLIP's sizes are ModelConfig's own text_* fields; its leaves
#: (like the VAE's and the UNet's) are float32 and cast at the jit boundary. A
#: language-model tower is held in bfloat16: at 2 bytes a parameter its
#: chip's share is 10 GB, at 4 it is more than the chip.
TEXT_TOWERS = {
    "clip": TextTower("dcr_tpu.models.clip_text:CLIPTextModel", None,
                      "float32", True),
    "longcat_flash": TextTower(
        "dcr_tpu.models.longcat_flash:LongcatFlashTextTower", "longcat",
        "bfloat16", False),
    "openpangu_ultra_moe": TextTower(
        "dcr_tpu.models.openpangu_ultra_moe:OpenPanguUltraMoETextTower",
        "openpangu", "bfloat16", False),
    "solar_open2": TextTower(
        "dcr_tpu.models.solar_open2:SolarOpen2TextTower", "solar",
        "bfloat16", False),
}


@dataclass
class ModelConfig:
    """Flagship diffusion-stack dimensions (SD-2.1 base by default).

    The reference never defines these (it loads HF diffusers checkpoints,
    diff_train.py:370-408); here they are explicit so tiny test/smoke variants are
    first-class and a from-scratch UNet (reference --unet_from_scratch,
    diff_train.py:237-243) is just a config.
    """

    # UNet2DCondition
    sample_size: int = 32              # latent spatial size = resolution // 8
    in_channels: int = 4
    out_channels: int = 4
    block_out_channels: tuple[int, ...] = (320, 640, 1280, 1280)
    layers_per_block: int = 2
    attention_head_dim: int = 64
    # SD-1.x fixes the head COUNT instead (8 heads, head_dim = ch/8); when
    # set, attention_head_dim is ignored. Needed to express the
    # CompVis/stable-diffusion-v1-4 UNet the reference's mitigation driver
    # is hardcoded to (sd_mitigation.py:46).
    attention_num_heads: Optional[int] = None  # Optional[...] so CLI coercion works
    cross_attention_dim: int = 1024
    transformer_layers: int = 1
    # SD-2.x transformers project with linears; SD-1.x uses 1x1 convs
    use_linear_projection: bool = True
    norm_num_groups: int = 32
    flash_attention: bool = True       # Pallas kernel when on TPU, XLA fallback otherwise
    # Spatial self-attention switches to sequence/context parallelism over the
    # mesh's `seq` axis when the token count reaches this AND the mesh's seq
    # axis is >1. 4096 = 512px latents, where the S×S weight tensor stops
    # fitting comfortably on one chip.
    seq_parallel_min_seq: int = 4096
    # "ring" (K/V rotate via ppermute, ops/ring_attention.py) or "ulysses"
    # (all_to_all seq<->heads re-shard, full-sequence flash per head group,
    # ops/ulysses_attention.py; needs heads % seq == 0, else falls back to
    # ring at the dispatch site).
    seq_parallel_mode: str = "ring"
    # VAE
    vae_block_out_channels: tuple[int, ...] = (128, 256, 512, 512)
    vae_layers_per_block: int = 2
    vae_latent_channels: int = 4
    vae_scaling_factor: float = 0.18215
    # The text tower, a name of TEXT_TOWERS: "clip" (models/clip_text.py; the
    # text_hidden_size / text_layers / text_heads / text_act fields below are
    # its sizes) or a frozen language model whose sizes are a block of its
    # own and whose output is projected to cross_attention_dim:
    # "longcat_flash" (models/longcat_flash.py, `longcat`),
    # "openpangu_ultra_moe" (models/openpangu_ultra_moe.py, `openpangu`) or
    # "solar_open2" (models/solar_open2.py, `solar`).
    # text_vocab_size and text_max_length belong to whichever tower is chosen.
    text_tower: str = "clip"
    longcat: LongcatFlashConfig = field(default_factory=LongcatFlashConfig)
    openpangu: OpenPanguUltraMoEConfig = field(
        default_factory=OpenPanguUltraMoEConfig)
    solar: SolarOpen2Config = field(default_factory=SolarOpen2Config)
    # CLIP text encoder (OpenCLIP ViT-H text tower for SD-2.1)
    text_vocab_size: int = 49408
    text_hidden_size: int = 1024
    text_layers: int = 23
    text_heads: int = 16
    text_max_length: int = 77
    # MLP activation of the text tower: SD-2.x's OpenCLIP ViT-H tower uses
    # exact GELU (HF text_encoder config hidden_act="gelu"); OpenAI CLIP-B/L
    # towers use quick_gelu (x·σ(1.702x)). Getting this wrong silently drifts
    # every activation when real weights are loaded.
    text_act: str = "gelu"
    # diffusion process
    num_train_timesteps: int = 1000
    beta_schedule: str = "scaled_linear"
    beta_start: float = 0.00085
    beta_end: float = 0.012
    prediction_type: str = "epsilon"   # or "v_prediction"

    @staticmethod
    def sd1x() -> "ModelConfig":
        """SD-1.4/1.5 stack: fixed 8-head attention, 1x1-conv transformer
        projections, CLIP ViT-L/14 text tower (quick_gelu, 768-d). The
        reference's mitigation driver targets this model family
        (sd_mitigation.py:46: CompVis/stable-diffusion-v1-4)."""
        return ModelConfig(
            sample_size=64,
            attention_head_dim=0,
            attention_num_heads=8,
            use_linear_projection=False,
            cross_attention_dim=768,
            text_hidden_size=768,
            text_layers=12,
            text_heads=12,
            text_act="quick_gelu",
        )

    @staticmethod
    def tiny() -> "ModelConfig":
        """CPU-runnable smoke config (BASELINE.json config 1)."""
        return ModelConfig(
            sample_size=8,
            block_out_channels=(32, 64),
            layers_per_block=1,
            attention_head_dim=8,
            cross_attention_dim=32,
            norm_num_groups=8,
            vae_block_out_channels=(16, 32),
            vae_layers_per_block=1,
            text_vocab_size=1000,
            text_hidden_size=32,
            text_layers=2,
            text_heads=2,
            text_max_length=16,
            flash_attention=False,
        )


@dataclass
class DataConfig:
    """Dataset + duplication + conditioning knobs (reference datasets.py:32-152)."""

    train_data_dir: str = ""
    resolution: int = 256
    center_crop: bool = True
    random_flip: bool = True
    class_prompt: str = "nolevel"          # CONDITIONING_REGIMES
    instance_prompt: str = "an image"      # nolevel constant caption
    duplication: str = "nodup"             # DUPLICATION_REGIMES
    weight_pc: float = 0.1                 # fraction of samples duplicated
    dup_weight: int = 5                    # sampling weight for duplicated samples
    caption_jsons: tuple[str, ...] = ()    # blip/ogcap caption tables
    trainspecial: str = "none"             # TRAIN_MITIGATIONS
    trainspecial_prob: float = 0.1
    trainsubset: int = -1                  # -1: full dataset (reference --trainsubset)
    rand_caption_tokens: int = 4           # instancelevel_random token count
    num_workers: int = 8
    seed: int = 42


@dataclass
class FaultToleranceConfig:
    """Recovery knobs (core/resilience.py, utils/faults.py). The defaults keep
    the seed's fail-fast semantics: budgets of 0 mean the first bad sample /
    non-finite loss is fatal exactly as before — recovery is opt-in per run.
    """

    # data path: extra decode attempts per sample before it counts as bad
    decode_retries: int = 1
    # fraction of an epoch's samples allowed to fail decode before aborting;
    # 0 = first bad sample is fatal (seed behavior). Failed samples are
    # replaced by a deterministic redraw from the same epoch plan and recorded
    # in <output_dir>/quarantine.jsonl.
    max_bad_sample_frac: float = 0.0
    # non-finite loss: restore the last good checkpoint, fast-forward the
    # loader past the offending data window, continue — at most this many
    # times per run; 0 = fail fast (seed behavior).
    max_rollbacks: int = 0
    # write/verify per-step content manifests (tree + array checksums) next to
    # each orbax save; restore walks back to the newest VALID checkpoint.
    # COST: manifest hashing is a synchronous device->host pass over the full
    # state at every save (it must snapshot before the async write starts) —
    # disable on throughput-critical pods if save cadence is tight.
    verify_checkpoints: bool = True
    # transient file-I/O retry attempts (tokenizer/caption/weights reads)
    io_retries: int = 3
    retry_base_delay: float = 0.05
    retry_max_delay: float = 2.0
    # soft per-stage time budget for eval pipeline stages (watchdog warning
    # only; 0 disables)
    stage_deadline_secs: float = 0.0
    # multi-host: wall-clock budget for cross-host sync points (barriers and
    # fault-agreement allgathers); overrun raises a typed BarrierTimeout
    # instead of hanging forever. 0 = wait forever (single-host default).
    barrier_timeout_s: float = 0.0
    # multi-host: collective-hang watchdog — no step-boundary heartbeat for
    # this long => dump all thread stacks + the last agreement word and abort
    # with exit code 89 (coordination.EXIT_HANG) so the scheduler restarts the
    # pod instead of letting it stall. 0 = disabled; env DCR_HANG_TIMEOUT_S
    # overrides (set it comfortably above the slowest legitimate step gap,
    # including eval/sampling pauses).
    hang_timeout_s: float = 0.0


@dataclass
class WarmCacheConfig:
    """Persistent executable cache + warm-start readiness (core/warmcache.py).

    With ``dir`` set, every AOT-lowered program (train step, params-finite
    check, serve buckets, serve text encoder, bulk samplers, eval extractor)
    is served from a fingerprint-keyed on-disk executable cache: a respawned
    worker or resumed trainer loads compiled code instead of paying XLA
    again. The fingerprint covers avals/shardings/donation/static
    config/lowered HLO plus topology and jax/jaxlib versions, so a stale or
    mismatched entry is detected — and quarantined — never loaded blind.
    ``dir`` may be shared by a whole fleet (atomic last-writer-wins entries).
    """

    dir: str = ""             # "" = no persistence (AOT warm start still runs
    #                           where a readiness phase exists, e.g. serve)
    # serve only: precompile the warm-manifest bucket set (plus the default
    # bucket) before reporting ready / publishing a ready lease. Off = the
    # pre-dcr-warm behavior (lazy compile on first use; /healthz never
    # reports "warming").
    warm_start: bool = True


@dataclass
class PipeConfig:
    """Pipelined training (dcr_tpu/diffusion/encode_stage.py): split the
    fused train step into a pure denoiser+optimizer hot step and a frozen-
    encoder producer stage that runs VAE-encode (+ text-encode when the text
    encoder is frozen) one-or-more steps ahead of the trainer, feeding a
    bounded device-side prefetch ring. With ``enabled=False`` (the default)
    the trainer builds the ORIGINAL fused step — disabled mode is
    bit-identical by construction (the fused program's HLO digest in
    compile_manifest.json does not move). RNG stream ownership is explicit:
    the producer owns the ``vae_sample`` stream, the denoiser owns
    ``noise``/``timesteps``/``emb_noise``/``mixup_*`` — so the q-sample
    draws are unchanged between fused and pipelined runs.

    ``latent_cache`` points at a persistent latent cache directory
    (data/latent_cache.py, built by ``dcr-precompute-latents``): the
    producer then reads precomputed VAE posterior moments + text embeddings
    instead of running the encoders at all — one precompute amortizes
    encoder work across every duplication/mitigation regime trained against
    the same images (the paper's experiment matrix). Setting it implies
    pipelined mode."""

    enabled: bool = False
    # prefetch ring depth: encoded batches the producer may run ahead of the
    # denoiser (device memory for `depth` latent/ctx batches)
    depth: int = 2
    # persistent latent cache dir ("" = live encoders). Keyed on params
    # fingerprint + dataset + resolution; verified before load, corrupt
    # shards are quarantined and their samples re-encoded live.
    latent_cache: str = ""
    # samples per cache shard at precompute time: the blast radius of one
    # corrupt/torn shard (its indices degrade to live recompute; losing
    # EVERY shard is a typed error, so small datasets benefit from small
    # shards)
    cache_shard_size: int = 512


@dataclass
class FastSampleConfig:
    """Training-free sampler acceleration (dcr_tpu/sampling/fastsample.py):
    a host-computed per-step plan of ``full | reuse`` entries à la PFDiff —
    full steps run the CFG UNet call and bank the guided score, reuse steps
    skip the UNet and substitute the banked score (first-order reuse, or
    second-order past-difference extrapolation once two scores are banked).
    The plan is static config: each (bucket, plan) is its own compiled
    program, and with ``enabled=False`` the samplers build their original
    scan body bit-identically. Quality is gated by tools/bench_fastsample.py
    (SSCD similarity + FID of fast-vs-reference output, banked as
    BENCH_FASTSAMPLE.json).
    """

    enabled: bool = False
    # fraction of steps replaced by score reuse; the effective denoiser-call
    # reduction is ~1/(1-ratio) (0.5 => ~2x fewer UNet calls). Capped at
    # fastsample.MAX_REUSE_RATIO (0.75); first two + final steps always full.
    reuse_ratio: float = 0.5
    # 1 = plain reuse of the last banked score; 2 = linear extrapolation
    # from the last two (PFDiff's past-difference form) — strictly better
    # fidelity at the same call count, the default.
    order: int = 2


@dataclass
class RiskConfig:
    """Online copy-risk scoring (dcr_tpu/obs/copyrisk.py): SSCD gen↔train
    similarity — the papers' headline replication measurement — computed
    LIVE against a train-set embedding index instead of in offline eval
    batch jobs. With ``index_path`` set, the serve worker scores every
    generated batch (``copy_risk`` on each /generate response, ``POST
    /check`` for ad-hoc queries, ``dcr_copy_risk_*`` telemetry, bounded
    evidence dumps over ``threshold``) and the trainer scores its periodic
    sample grids into ``risk/*`` MetricWriter gauges. A failed index load
    degrades to scoring-disabled — it never blocks admission or training.
    """

    # train-set embedding dump: search/embed.py .npz format, or the
    # reference toolchain's pickle {'features','indexes'} ("" = disabled)
    index_path: str = ""
    # dcr-store alternative: a built sharded embedding store (dcr-search
    # build). Takes precedence over index_path; scoring runs through the
    # mesh-sharded search/topk engine, so the corpus no longer has to fit
    # one device-resident matmul operand.
    store_dir: str = ""
    segment_rows: int = 0     # rows per device segment for store mode; 0=auto
    # dcr-ann: score through the store's IVF + int8 approximate tier with
    # exact f32 re-ranking (requires a trained index — `dcr-search
    # train-ivf --search.ivf_normalize`). The exact engine stays the
    # default: risk scores feed a threshold, and the ann tier trades
    # bounded recall for sublinear corpus cost only when asked.
    ann: bool = False
    nprobe: int = 8           # probed lists per query in ann mode
    # SSCD backbone weights (torch state dict / TorchScript archive,
    # converted on load). "" = deterministic random init — self-consistent
    # (an index embedded with the same init scores correctly) but NOT
    # comparable to reference SSCD numbers.
    weights_path: str = ""
    # max_sim >= threshold flags the generation as a probable copy. 0.5 is
    # the papers' SSCD replication threshold ("Diffusion Art or Digital
    # Forgery?" §4); raise it for random-init smoke indexes where the
    # background similarity of unrelated images is higher.
    threshold: float = 0.5
    top_k: int = 1            # nearest train keys kept per generation
    image_size: int = 224     # SSCD input crop (the embedding dump must match)
    # flagged-generation evidence dumps (image + nearest train key), bounded
    # per process; "" = <logdir>/risk_evidence when a logdir exists
    evidence_dir: str = ""
    max_evidence: int = 32    # 0 disables evidence dumps


@dataclass
class IngestConfig:
    """dcr-live streaming provenance ingest (search/livestore.py + serve/
    ingest.py): with ``enabled`` and ``risk.store_dir`` set, every scored
    generation's SSCD embedding is enqueued on a bounded queue and appended
    to a crash-safe WAL tier in front of the committed store; compaction
    periodically folds the WAL into shards and publishes a new snapshot.
    The response path only ever enqueues (never blocks) — a full queue
    drops-and-counts (``dcr_ingest_dropped_total``)."""

    enabled: bool = False
    # response-path queue bound (rows). Overflow drops rows, never blocks.
    queue_max: int = 1024
    batch_rows: int = 16      # rows folded into one WAL record / fsync
    seal_rows: int = 4096     # rows per WAL segment before it seals
    # acked-but-uncompacted rows that trigger compaction into committed
    # shards + a new snapshot. 0 = never auto-compact (WAL-only; recovery
    # replays the whole tail).
    compact_rows: int = 2048
    lease_s: float = 10.0     # writer-lease TTL (stale-takeover horizon)


@dataclass
class SloConfig:
    """dcr-slo (dcr_tpu/obs/slo.py): declarative service-level objectives
    over the live provenance plane, evaluated by the fleet supervisor's
    monitor loop from the existing worker scrape. Each objective compares
    one signal (availability, queue-wait p99, shed rate, ingest lag, ANN
    staleness, online ANN recall, copy-risk scoring coverage) against its
    target and tracks the classic multi-window burn rate: the fraction of
    recent samples violating the target, divided by the error ``budget``.
    ``ok -> warn`` on the short window alone; ``-> breach`` only when BOTH
    windows burn (a transient spike cannot page), back to ``ok`` below
    ``recover_burn`` (hysteresis). State is exported as
    ``dcr_slo_{burn_rate,state,breach_total}`` metrics, ``GET /slo``, and
    ``slo/breach``/``slo/recover`` trace events; a breach sustained past
    ``dump_after_s`` dumps the flight recorder."""

    enabled: bool = True
    short_window_s: float = 60.0   # fast-burn window (detection latency)
    long_window_s: float = 300.0   # slow-burn window (spike suppression)
    # burn thresholds, in units of budget-consumption rate: burn 1.0 means
    # the window is violating at exactly the budgeted fraction
    warn_burn: float = 1.0
    breach_burn: float = 2.0
    recover_burn: float = 0.5      # must drop BELOW warn_burn (hysteresis)
    budget: float = 0.1            # allowed bad-sample fraction at burn 1.0
    dump_after_s: float = 120.0    # sustained breach before a flight-rec dump
    # objective targets; <= 0 disables that objective. The queue-wait p99
    # objective reuses fleet.slo_queue_wait_p99_s (the shed threshold) as
    # its target so alerting and shedding can never disagree.
    availability_min: float = 0.75    # stale-scrape-aware alive fraction
    shed_rate_max: float = 0.05       # shed/(accepted+shed) per window
    ingest_lag_s_max: float = 30.0    # queue lag OR oldest-unfolded row age
    ann_staleness_rows_max: float = 50000.0   # store rows not in IVF lists
    recall_min: float = 0.80          # rolling online recall@k (probe)
    coverage_min: float = 0.95        # scored generations / completed
    # online recall probe (obs/recall_probe.py): every Nth ANN scoring call
    # re-runs the batch through the shadow-exact oracle (all lists probed —
    # the f32 re-rank is exact, so the candidate set is the whole corpus)
    recall_probe_every_n: int = 32
    recall_probe_k: int = 10
    recall_probe_window: int = 64     # rolling samples behind the gauge


@dataclass
class OptimConfig:
    learning_rate: float = 5e-6
    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    adam_weight_decay: float = 1e-2
    adam_epsilon: float = 1e-8
    max_grad_norm: float = 1.0
    lr_scheduler: str = "constant_with_warmup"
    lr_warmup_steps: int = 5000
    gradient_accumulation_steps: int = 1
    scale_lr: bool = False
    # 8-bit blockwise moment state (reference --use_8bit_adam via CUDA-only
    # bitsandbytes, diff_train.py:424-435; TPU-native core/adam8bit.py)
    use_8bit_adam: bool = False


@dataclass
class TrainConfig:
    output_dir: str = "runs/dcr"
    pretrained_model: str = ""             # HF-layout checkpoint dir to finetune from
    seed: int = 42
    # seeds the periodic in-training sample grids independently of the train
    # seed (reference --generation_seed, diff_train.py:121,579)
    generation_seed: int = 1024
    train_batch_size: int = 16             # per-device
    max_train_steps: int = 100_000
    num_train_epochs: int = 100
    train_text_encoder: bool = False
    unet_from_scratch: bool = False
    mixed_precision: str = "bf16"          # "no" | "bf16"
    remat: bool = False                    # jax.checkpoint the UNet fwd (512px+)
    ema_decay: float = 0.0                 # 0 disables EMA
    # train-time embedding mitigations (reference diff_train.py:637-642)
    rand_noise_lam: float = 0.0
    mixup_noise_lam: float = 0.0
    # cadence (reference diff_train.py:709-716; README.md:33)
    save_steps: int = 500                  # sample-image grids
    modelsavesteps: int = 1000             # checkpoints
    log_every: int = 50
    use_wandb: bool = False                # wandb sink (jsonl/tb always on)
    checkpoints_total_limit: int = 3
    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    optim: OptimConfig = field(default_factory=OptimConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    fault: FaultToleranceConfig = field(default_factory=FaultToleranceConfig)
    warm: WarmCacheConfig = field(default_factory=WarmCacheConfig)
    risk: RiskConfig = field(default_factory=RiskConfig)
    pipe: PipeConfig = field(default_factory=PipeConfig)


@dataclass
class SampleConfig:
    """Bulk sampling (reference diff_inference.py:203-243, sd_mitigation.py)."""

    model_path: str = ""
    iternum: int = -1                      # select checkpoint_<step>; -1 = final
    savepath: str = ""
    num_batches: int = 50
    im_batch: int = 10                     # images per prompt per batch
    resolution: int = 256
    num_inference_steps: int = 50
    guidance_scale: float = 7.5
    sampler: str = "dpm++"                 # "ddim" | "dpm++" | "ddpm"
    seed: int = 42
    # inference-time mitigations
    rand_noise_lam: float = 0.0            # gaussian noise on prompt embeddings
    rand_augs: str = "none"                # INFERENCE_AUGS
    rand_aug_repeats: int = 2              # reference diff_inference.py:218
    mesh: MeshConfig = field(default_factory=MeshConfig)
    warm: WarmCacheConfig = field(default_factory=WarmCacheConfig)
    fast: FastSampleConfig = field(default_factory=FastSampleConfig)


@dataclass
class FleetConfig:
    """Multi-worker serving fleet (dcr_tpu/serve/supervisor.py): one
    supervisor process owns the HTTP front end, the admission queue, and the
    durable in-flight request journal; N device-worker subprocesses join via
    heartbeat-leased membership and pull bucket-coherent batches over
    per-worker dispatch channels. A worker that dies — crash, preemption
    (83), hang watchdog (89) — has its journaled in-flight requests requeued
    onto survivors (safe: every image is a pure function of (ckpt, prompt,
    seed, bucket)) and is respawned with bounded backoff.
    """

    workers: int = 0          # >0 runs dcr-serve as a fleet supervisor
    worker_index: int = -1    # >=0 marks a fleet WORKER process (set by the
    #                           supervisor when spawning; not set by hand)
    dir: str = ""             # control-plane dir: leases, journal, worker logs
    #                           ("" = a directory beside --logdir or a tmpdir)
    heartbeat_s: float = 1.0  # worker lease renewal period
    lease_s: float = 5.0      # lease expiry: a worker silent this long is dead
    # supervisor-side bound on one dispatched batch (covers compile on first
    # use); an overrun declares the worker hung, SIGKILLs it, and requeues
    dispatch_timeout_s: float = 600.0
    max_attempts: int = 3     # dispatch attempts per request before a typed 500
    respawn_max: int = 3      # consecutive spawn failures before a slot retires
    respawn_base_delay_s: float = 0.5
    respawn_max_delay_s: float = 10.0
    spawn_timeout_s: float = 600.0  # worker must publish its lease within this
    # load shedding: reject admission with 503 + Retry-After while queue-wait
    # p99 (from the telemetry registry) exceeds this AND a backlog exists.
    # 0 disables shedding.
    slo_queue_wait_p99_s: float = 0.0
    shed_retry_after_s: float = 5.0  # Retry-After hint on shed responses
    # fleet metrics aggregation (dcr-scope): the supervisor scrapes each
    # live worker's /metrics?format=prometheus on this cadence and serves
    # the merged, worker="N"-labeled exposition from the front end. The
    # per-target socket timeout bounds a dead worker's cost per cycle —
    # the merged endpoint itself never blocks on a worker.
    scrape_period_s: float = 2.0
    scrape_timeout_s: float = 2.0


@dataclass
class ServeConfig:
    """Online generation service (dcr_tpu/serve/): a resident compiled sampler
    behind an HTTP front end with dynamic batching, an LRU prompt-embedding
    cache, bounded-queue admission control, and SIGTERM graceful drain.

    There is no reference equivalent — every generation path in somepago/DCR
    is offline batch. The serving defaults (resolution/steps/guidance/sampler)
    define the *default request bucket*; per-request overrides that match an
    already-compiled bucket reuse it, anything else compiles once on first use.
    """

    model_path: str = ""
    iternum: int = -1                      # select checkpoint_<step>; -1 = final
    host: str = "127.0.0.1"
    port: int = 8000
    # default generation bucket (per-request overrides allowed)
    resolution: int = 256
    num_inference_steps: int = 50
    guidance_scale: float = 7.5
    sampler: str = "dpm++"                 # "ddim" | "dpm++" | "ddpm"
    rand_noise_lam: float = 0.0            # inference-time mitigation (Newpipe)
    # batching: every batch is padded to exactly max_batch requests — ONE
    # compiled program per bucket, and (with per-request PRNG keys) results
    # that are bit-independent of batch composition. A partial batch is
    # flushed once its oldest request has waited max_wait_ms.
    max_batch: int = 8
    max_wait_ms: float = 50.0
    # admission control: pending requests beyond this are rejected with a
    # typed overload error (HTTP 503) instead of growing latency unboundedly
    queue_depth: int = 64
    cache_entries: int = 1024              # LRU prompt-embedding cache capacity
    # resident compiled-sampler budget: per-request bucket overrides beyond
    # this many DISTINCT (resolution, steps, guidance, sampler, λ) tuples are
    # rejected with a typed 503 — compiled programs are never evicted, so an
    # unbounded registry would let clients grow memory without limit
    max_compiled_buckets: int = 8
    request_timeout_s: float = 600.0       # per-request wait bound in the handler
    # wedged-sampler watchdog: a single batch step exceeding this trips the
    # coordination hang path (stack dump + exit 89) instead of hanging the
    # port forever. 0 = disabled.
    hang_timeout_s: float = 0.0
    logdir: str = ""                       # MetricWriter sink ("" = off)
    seed: int = 42                         # folds into per-request keys
    mesh: MeshConfig = field(default_factory=MeshConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    warm: WarmCacheConfig = field(default_factory=WarmCacheConfig)
    risk: RiskConfig = field(default_factory=RiskConfig)
    # dcr-live: stream scored generations' embeddings into risk.store_dir
    ingest: IngestConfig = field(default_factory=IngestConfig)
    # fast default bucket: with fast.enabled the default GenBucket carries
    # the reuse plan (per-request overrides can still request a dense or
    # differently-planned bucket within the compiled-bucket budget)
    fast: FastSampleConfig = field(default_factory=FastSampleConfig)
    # dcr-slo: declarative SLOs evaluated by the fleet supervisor
    slo: SloConfig = field(default_factory=SloConfig)


def validate_serve_config(cfg: ServeConfig) -> None:
    from dcr_tpu.sampling.sampler import SAMPLERS

    if cfg.sampler not in SAMPLERS:
        raise ValueError(f"serve sampler must be one of {tuple(SAMPLERS)}, "
                         f"got {cfg.sampler!r}")
    if cfg.max_batch < 1:
        raise ValueError("serve max_batch must be >= 1")
    if cfg.queue_depth < 1:
        raise ValueError("serve queue_depth must be >= 1")
    if cfg.max_wait_ms < 0:
        raise ValueError("serve max_wait_ms must be >= 0")
    if cfg.cache_entries < 0:
        raise ValueError("serve cache_entries must be >= 0")
    if cfg.max_compiled_buckets < 1:
        raise ValueError("serve max_compiled_buckets must be >= 1")
    f = cfg.fleet
    if f.workers < 0:
        raise ValueError("fleet.workers must be >= 0")
    if f.workers > 0 and f.worker_index >= 0:
        raise ValueError("fleet.workers and fleet.worker_index are mutually "
                         "exclusive (supervisor vs worker role)")
    if f.workers > 0 or f.worker_index >= 0:
        if f.heartbeat_s <= 0 or f.lease_s <= f.heartbeat_s:
            raise ValueError("fleet.lease_s must exceed fleet.heartbeat_s > 0 "
                             "(a lease shorter than its renewal period "
                             "expires between heartbeats)")
        if f.dispatch_timeout_s <= 0:
            raise ValueError("fleet.dispatch_timeout_s must be > 0 (an "
                             "unbounded dispatch turns a hung worker into a "
                             "hung fleet)")
        if f.max_attempts < 1:
            raise ValueError("fleet.max_attempts must be >= 1")
        if f.respawn_max < 0:
            raise ValueError("fleet.respawn_max must be >= 0")
        if f.scrape_period_s <= 0 or f.scrape_timeout_s <= 0:
            raise ValueError("fleet.scrape_period_s and fleet.scrape_timeout_s"
                             " must be > 0 (an unbounded scrape turns a dead "
                             "worker into a hung /metrics)")
    validate_risk_config(cfg.risk)
    validate_ingest_config(cfg)
    validate_fast_config(cfg.fast)
    validate_slo_config(cfg.slo)


def validate_slo_config(s: SloConfig) -> None:
    if not s.enabled:
        return
    if s.short_window_s <= 0 or s.long_window_s <= 0:
        raise ValueError("slo windows must be > 0 (a zero-width window has "
                         "no samples to burn)")
    if s.long_window_s < s.short_window_s:
        raise ValueError("slo.long_window_s must be >= slo.short_window_s "
                         "(the long window exists to veto short-window "
                         "spikes; inverted windows would breach on noise)")
    if s.budget <= 0 or s.budget > 1:
        raise ValueError("slo.budget must be in (0, 1]: the allowed "
                         "bad-sample fraction at burn rate 1.0")
    if s.breach_burn < s.warn_burn:
        raise ValueError("slo.breach_burn must be >= slo.warn_burn "
                         "(breach is a worse state than warn)")
    if s.recover_burn >= s.warn_burn:
        raise ValueError("slo.recover_burn must be < slo.warn_burn: "
                         "recovery needs hysteresis or the state flaps at "
                         "the threshold")
    if s.dump_after_s < 0:
        raise ValueError("slo.dump_after_s must be >= 0")
    if s.recall_probe_every_n < 1:
        raise ValueError("slo.recall_probe_every_n must be >= 1")
    if s.recall_probe_k < 1 or s.recall_probe_window < 1:
        raise ValueError("slo.recall_probe_k and slo.recall_probe_window "
                         "must be >= 1")


def validate_ingest_config(cfg: ServeConfig) -> None:
    i = cfg.ingest
    if not i.enabled:
        return
    if not cfg.risk.store_dir:
        raise ValueError(
            "ingest.enabled requires risk.store_dir: live ingest appends to "
            "the sharded embedding store the risk index scores against "
            "(a dense risk.index_path dump has no append path)")
    if i.queue_max < 1:
        raise ValueError("ingest.queue_max must be >= 1")
    if i.batch_rows < 1:
        raise ValueError("ingest.batch_rows must be >= 1")
    if i.seal_rows < 1:
        raise ValueError("ingest.seal_rows must be >= 1")
    if i.compact_rows < 0:
        raise ValueError("ingest.compact_rows must be >= 0 (0 disables "
                         "auto-compaction)")
    if i.lease_s <= 0:
        raise ValueError("ingest.lease_s must be > 0 (the stale-writer "
                         "takeover horizon)")


def validate_fast_config(f: FastSampleConfig) -> None:
    from dcr_tpu.sampling.fastsample import MAX_REUSE_RATIO

    if not 0.0 <= f.reuse_ratio <= MAX_REUSE_RATIO:
        raise ValueError(
            f"fast.reuse_ratio must be in [0, {MAX_REUSE_RATIO}], "
            f"got {f.reuse_ratio}")
    if f.order not in (1, 2):
        raise ValueError(f"fast.order must be 1 or 2, got {f.order}")


def validate_risk_config(r: RiskConfig) -> None:
    if r.top_k < 1:
        raise ValueError("risk.top_k must be >= 1")
    if r.image_size < 16:
        raise ValueError("risk.image_size must be >= 16 (the SSCD backbone "
                         "downsamples 32x; tiny crops degenerate)")
    if not r.threshold == r.threshold:   # NaN compares unequal to itself
        raise ValueError("risk.threshold must be a number, not NaN")
    if r.max_evidence < 0:
        raise ValueError("risk.max_evidence must be >= 0")
    if r.ann and not r.store_dir:
        raise ValueError("risk.ann needs risk.store_dir (the IVF tier is "
                         "an index over a built store — the dump-file path "
                         "is exact-only)")
    if r.nprobe < 1:
        raise ValueError("risk.nprobe must be >= 1")


def validate_pipe_config(cfg: "TrainConfig") -> None:
    p = cfg.pipe
    if p.depth < 1:
        raise ValueError("pipe.depth must be >= 1 (the prefetch ring needs "
                         "at least one slot)")
    if p.cache_shard_size < 1:
        raise ValueError("pipe.cache_shard_size must be >= 1")
    if p.latent_cache:
        # cache-fed training freezes ONE realization per image — of the
        # caption/ctx AND of the pixel transform. Regimes that must redraw
        # either per occurrence cannot be served from it (the posterior
        # MOMENTS themselves are regime-independent; the per-occurrence
        # posterior sample still draws live).
        if cfg.train_text_encoder:
            raise ValueError(
                "pipe.latent_cache requires train_text_encoder=False: the "
                "cache replaces the frozen text encoder's output; a trained "
                "text encoder must run live (use pipe.enabled without a "
                "cache)")
        if cfg.data.trainspecial != "none":
            raise ValueError(
                "pipe.latent_cache is incompatible with caption mitigations "
                "(data.trainspecial): they redraw captions per occurrence, "
                "but the cache holds one frozen text embedding per image")
        if cfg.data.duplication == "dup_image":
            raise ValueError(
                "pipe.latent_cache is incompatible with duplication="
                "'dup_image': that regime redraws a DIFFERENT caption per "
                "occurrence of a duplicated image, but the cache holds one "
                "frozen text embedding per image (dup_both/nodup are fine "
                "— their captions are deterministic per index)")
        if cfg.data.random_flip:
            raise ValueError(
                "pipe.latent_cache requires data.random_flip=false: the "
                "cache holds one pixel realization per image, a "
                "per-occurrence flip cannot be served from it")
        if not cfg.data.center_crop:
            raise ValueError(
                "pipe.latent_cache requires data.center_crop=true: "
                "center_crop=false draws a RANDOM crop per occurrence, "
                "which the cache would silently freeze to one realization")


@dataclass
class EvalConfig:
    """Replication metrics (reference diff_retrieval.py:124-182)."""

    query_dir: str = ""                    # generations
    values_dir: str = ""                   # train data
    pt_style: str = "sscd"                 # "sscd" | "dino" | "clip"
    arch: str = "resnet50_disc"
    # DINO ViT only: >1 takes the CLS feature of the layer-th-from-last
    # block, get_intermediate_layers semantics (reference --layer,
    # utils_ret.py:731-745)
    layer: int = 1
    similarity_metric: str = "dotproduct"  # "dotproduct" | "splitloss"
    batch_size: int = 64
    image_size: int = 224
    multiscale: bool = False
    num_loss_chunks: int = 1
    chunk_style: str = "max"               # splitloss chunk reduce; "cross" variant
    compute_fid: bool = True
    compute_clip_score: bool = True
    compute_complexity: bool = True
    galleries: bool = True
    gallery_topk: int = 10
    gallery_rows: int = 10
    gallery_max_rank: int = 200
    dup_weights_pickle: str = ""           # training sampling-weights file
    # pretrained checkpoint files (torch state dicts / TorchScript archives /
    # safetensors), converted on load via models/convert.py; empty = random
    # init (and metrics are NOT comparable to reference numbers)
    weights_path: str = ""                 # copy-detection backbone (SSCD/DINO/CLIP)
    inception_weights_path: str = ""       # pt_inception-2015-12-05 for FID
    clip_weights_path: str = ""            # OpenAI CLIP archive for the alignment score
    output_dir: str = "ret_plots"
    use_wandb: bool = False                # wandb sink (jsonl/tb always on)
    seed: int = 42
    mesh: MeshConfig = field(default_factory=MeshConfig)
    fault: FaultToleranceConfig = field(default_factory=FaultToleranceConfig)
    warm: WarmCacheConfig = field(default_factory=WarmCacheConfig)


@dataclass
class SearchConfig:
    """LAION-scale embedding search (reference embedding_search/).

    The dcr-store fields drive the sharded-store workflow (``dcr-search
    build/append/verify/query``): embeddings ingested once into a
    manifest-keyed sha256-verified shard store (``store_dir``), then
    queried through the mesh-sharded ``search/topk`` engine instead of the
    per-folder brute-force chunk loop."""

    parquet_path: str = ""
    laion_folder: str = ""
    gen_folder: str = ""
    embedding_out: str = ""      # default: <gen_folder>/embedding.npz
    out_path: str = "similarity_result.npz"
    num_chunks: int = 20
    batch_size: int = 128
    image_size: int = 224
    delete_tars: bool = False
    mesh: MeshConfig = field(default_factory=MeshConfig)
    # -- dcr-store: sharded embedding store + device-sharded top-k ----------
    store_dir: str = ""          # built store; "" = brute-force folder scan
    dumps: tuple[str, ...] = ()  # extra dump files/dirs for build/append
    shard_rows: int = 4096       # rows per store shard file (ingest unit)
    store_normalize: bool = False  # L2-normalize rows at ingest (cosine)
    top_k: int = 1               # nearest corpus keys kept per query
    query_batch: int = 64        # fixed compiled query-batch shape
    segment_rows: int = 0        # rows per device segment; 0 = auto
    # dcr-live: query the committed snapshot PLUS the WAL live tail (rows
    # acked by a streaming ingester but not yet compacted), merged
    live: bool = False
    # -- dcr-ann: IVF + int8 approximate tier (search/ann.py) ---------------
    ann: bool = False            # query via the ann tier (exact = default)
    n_lists: int = 64            # IVF coarse centroids (train-ivf)
    nprobe: int = 8              # probed lists per query (recall knob)
    ivf_iters: int = 10          # Lloyd iterations (train-ivf)
    ivf_seed: int = 0            # k-means init seed (determinism pin)
    ivf_train_rows: int = 0      # training subsample; 0 = whole store
    ivf_normalize: bool = False  # L2-normalize rows before train (cosine)
    shortlist_k: int = 32        # int8 shortlist per (query, segment)
    json_out: bool = False       # machine-readable `stats` output
    warm_dir: str = ""           # persistent executable cache (dcr-warm)
    logdir: str = ""             # trace.jsonl sink for search/* spans


# ---------------------------------------------------------------------------
# (de)serialization + CLI
# ---------------------------------------------------------------------------


def to_dict(cfg: Any) -> Any:
    if is_dataclass(cfg):
        return {f.name: to_dict(getattr(cfg, f.name)) for f in fields(cfg)}
    if isinstance(cfg, (list, tuple)):
        return [to_dict(v) for v in cfg]
    return cfg


def _coerce(value: Any, typ: Any) -> Any:
    origin = get_origin(typ)
    if origin in (tuple, list):
        args = get_args(typ)
        elem = args[0] if args else str
        if isinstance(value, str):
            value = [v for v in value.split(",") if v]
        out = [_coerce(v, elem) for v in value]
        return tuple(out) if origin is tuple else out
    if origin is not None and str(origin) == "typing.Union":  # Optional[...]
        args = [a for a in get_args(typ) if a is not type(None)]
        if value is None:
            return None
        return _coerce(value, args[0])
    if is_dataclass(typ):
        return from_dict(typ, value)
    if typ is bool:
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "y")
        return bool(value)
    if typ in (int, float, str):
        return typ(value)
    return value


def from_dict(cls: Type[T], d: dict) -> T:
    kwargs = {}
    fmap = {f.name: f for f in fields(cls)}
    for k, v in d.items():
        if k not in fmap:
            raise KeyError(f"unknown config key {k!r} for {cls.__name__}")
        kwargs[k] = _coerce(v, fmap[k].type if not isinstance(fmap[k].type, str) else _resolve(cls, fmap[k].name))
    return cls(**kwargs)


def _resolve(cls: Type, name: str) -> Any:
    # dataclass field types may be strings under `from __future__ import annotations`
    import typing

    hints = typing.get_type_hints(cls)
    return hints[name]


def save_config(cfg: Any, path: str | Path) -> None:
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(to_dict(cfg), indent=2, sort_keys=True) + "\n")


def load_config(cls: Type[T], path: str | Path) -> T:
    return from_dict(cls, json.loads(Path(path).read_text()))


def _set_nested(d: dict, dotted: str, value: str) -> None:
    parts = dotted.split(".")
    cur = d
    for p in parts[:-1]:
        cur = cur.setdefault(p, {})
    cur[parts[-1]] = value


def parse_cli(cls: Type[T], argv: Optional[Sequence[str]] = None, base: Optional[T] = None) -> T:
    """``--a.b.c=value`` style overrides on top of defaults (or ``--config=file.json``).

    Deliberately minimal: every field of the nested dataclass tree is addressable,
    nothing else is accepted — replacing ~40 hand-kept argparse flags per script in
    the reference.
    """
    argv = list(sys.argv[1:] if argv is None else argv)
    overrides: dict = {}
    cfg_path = None
    for arg in argv:
        if not arg.startswith("--"):
            raise SystemExit(f"unrecognized argument {arg!r} (expected --key=value)")
        key, eq, value = arg[2:].partition("=")
        if key == "config":
            cfg_path = value
        elif not eq:
            # bare `--flag` means true for booleans; _coerce rejects it loudly
            # for any non-bool field (int('true') -> ValueError naming the value)
            _set_nested(overrides, key, "true")
        else:
            _set_nested(overrides, key, value)
    if base is not None and cfg_path:
        raise SystemExit("--config cannot be combined with a programmatic base config")
    if base is not None:
        cfg = base
    elif cfg_path:
        cfg = load_config(cls, cfg_path)
    else:
        cfg = cls()
    merged = to_dict(cfg)

    def merge(dst: dict, src: dict) -> None:
        for k, v in src.items():
            if isinstance(v, dict) and isinstance(dst.get(k), dict):
                merge(dst[k], v)
            else:
                dst[k] = v

    merge(merged, overrides)
    return from_dict(cls, merged)


def run_name(cfg: TrainConfig) -> str:
    """Human-readable run directory name, compatible in spirit with the reference's
    output-dir mangling (diff_train.py:745-760) — but informational only: the
    source of truth is the serialized config.json next to the checkpoint."""
    d = cfg.data
    parts = [d.class_prompt, d.duplication]
    if d.duplication != "nodup":
        parts += [str(d.weight_pc), str(d.dup_weight)]
    if cfg.rand_noise_lam:
        parts.append(f"glam{cfg.rand_noise_lam}")
    if cfg.mixup_noise_lam:
        parts.append(f"mixlam{cfg.mixup_noise_lam}")
    if d.trainspecial != "none":
        parts.append(f"special_{d.trainspecial}_{d.trainspecial_prob}")
    if d.trainsubset > 0:
        parts.append(f"{d.trainsubset}subset")
    return "_".join(parts)


def validate_text_tower(m: ModelConfig) -> None:
    if m.text_tower not in TEXT_TOWERS:
        raise ValueError(
            f"model.text_tower must be one of {tuple(TEXT_TOWERS)}")
    name = TEXT_TOWERS[m.text_tower].block
    if name is None:
        return
    block = getattr(m, name)
    first, count = block.held_range()
    if first < 0 or count < 0 or first + count > block.n_routed_experts:
        raise ValueError(
            f"model.{name} holds routed experts [{first}, {first + count}) "
            f"of {block.n_routed_experts}: not a range of them")
    if block.experts_per_token() > block.router_outputs():
        raise ValueError(f"model.{name} chooses more experts a token than "
                         "the router has outputs")
    block.validate(name)


def validate_train_config(cfg: TrainConfig) -> None:
    """Cross-flag validation (reference diff_train.py:739-743)."""
    d = cfg.data
    if d.duplication not in DUPLICATION_REGIMES:
        raise ValueError(f"duplication must be one of {DUPLICATION_REGIMES}")
    if d.class_prompt not in CONDITIONING_REGIMES:
        raise ValueError(f"class_prompt must be one of {CONDITIONING_REGIMES}")
    if d.trainspecial not in TRAIN_MITIGATIONS:
        raise ValueError(f"trainspecial must be one of {TRAIN_MITIGATIONS}")
    if d.duplication == "dup_image" and d.class_prompt == "instancelevel_ogcap":
        # guarded invalid in the reference (diff_train.py:739)
        raise ValueError("dup_image requires multiple captions per image; ogcap has one")
    if d.trainspecial != "none" and d.class_prompt != "instancelevel_blip":
        # caption mitigations are blip-captions-only (reference diff_train.py:741-743)
        raise ValueError("trainspecial mitigations require class_prompt=instancelevel_blip")
    validate_risk_config(cfg.risk)
    validate_pipe_config(cfg)
    if cfg.model.seq_parallel_mode not in ("ring", "ulysses"):
        raise ValueError("seq_parallel_mode must be 'ring' or 'ulysses'")
    validate_text_tower(cfg.model)
    tower = TEXT_TOWERS[cfg.model.text_tower]
    if cfg.train_text_encoder and not tower.trainable:
        raise ValueError(
            "train_text_encoder=true is refused with model.text_tower="
            f"{cfg.model.text_tower}: the tower is held frozen in "
            f"{tower.held_dtype} (2 bytes a "
            "parameter); trained it costs 16 bytes a parameter (float32 "
            "weights, gradients and two Adam moments), which no cut of it "
            "fits on a chip beside the UNet's own optimizer state. Encode "
            "once with dcr-precompute-latents and train from the cache")
    ft = cfg.fault
    if ft.decode_retries < 0 or ft.max_rollbacks < 0:
        raise ValueError("fault.decode_retries/max_rollbacks must be >= 0")
    if not 0.0 <= ft.max_bad_sample_frac <= 1.0:
        raise ValueError("fault.max_bad_sample_frac must be in [0, 1]")
    if ft.io_retries < 1:
        raise ValueError("fault.io_retries must be >= 1")
