"""Experiment / model configuration.

Capability parity with the reference's config system
(/root/reference/src/train.py:26-44 ``ExperimentConfig``,
/root/reference/src/model.py:108-115 ``GPTConfig``,
/root/reference/launch.py:25-27 name-based resolution,
/root/reference/sample.py:49-65 JSON round-trip), redesigned:

- nested dataclasses with a generic JSON (de)serializer instead of the
  hand-rolled ``from_json``;
- a mesh spec (``MeshConfig``) making DP / FSDP / SP / TP axis sizes explicit
  instead of the hardcoded ``(n_devices // 8, 8)`` mesh (train.py:130);
- named registry populated by ``midgpt_tpu.configs``.
"""

from __future__ import annotations

import dataclasses
import json
import typing as tp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture. Superset of the reference GPTConfig (model.py:108-115):
    adds GQA (n_kv_head), SwiGLU (mlp), and kernel/remat knobs for the
    Llama-style family required by BASELINE.json."""

    block_size: int  # max sequence length
    vocab_size: int
    n_layer: int
    n_head: int
    n_embd: int
    dropout: float = 0.0
    n_kv_head: tp.Optional[int] = None  # None => MHA (= n_head); < n_head => GQA
    mlp: str = "gelu"  # "gelu" (GPT-2, 4x) | "swiglu" (Llama) | "moe"
    # (Switch-style top-1 mixture of GELU experts; expert-parallel over
    # the 'tensor' mesh axis — see models/gpt.MoEMLP) | "experts"
    # (dropless top-k SwiGLU experts — models/gpt.ExpertMLP, below)
    moe_experts: int = 8  # experts per MoE layer (mlp="moe")
    moe_top_k: int = 1  # experts per token: 1 = Switch, 2 = GShard-style
    # (renormalized top-2 gates; aux loss tracks first choices)
    # per-row capacity factor: C = ceil(cf * top_k * T / E) — K claims per
    # token share the expert buffers, so capacity scales with top_k
    # (models/gpt.MoEMLP.__call__)
    moe_capacity: float = 1.25
    moe_aux_weight: float = 0.01  # load-balance aux loss weight (train)
    mlp_ratio: float = 4.0  # hidden = ratio * n_embd (swiglu: per-branch width)
    # exact hidden width; None = ratio * n_embd, with FRACTIONAL products
    # rounded up to a multiple of 256 (Llama's multiple_of rule; also the
    # MXU-friendly width — r3). Set explicitly to pin any width, e.g. to
    # restore a checkpoint trained before the rounding rule existed.
    mlp_hidden: tp.Optional[int] = None
    rope_base: float = 10000.0
    qk_norm: bool = True  # per-head QK-LayerNorm (model.py:52-53)
    tie_embeddings: bool = False  # True = one shared param (true tying);
    # False = reference semantics: shared init, independent params
    # (model.py:134-138, SURVEY.md 2.3)
    # "fused" = projection-natural QK-LN+RoPE+flash (ops/fused_attn);
    # "auto" prefers it on TPU when shapes allow
    # ring = streaming K/V ring over 'sequence'; ulysses = all-to-all
    # head<->sequence trade (parallel/ulysses.py — exact attention +
    # exact dropout, needs H % S == 0 and tensor == 1)
    attn_impl: str = "auto"  # auto | naive | flash | ring | ulysses | fused
    ring_schedule: str = "zigzag"  # zigzag (balanced) | standard; zigzag
    # auto-falls back to standard when T doesn't divide 2*sequence
    norm_impl: str = "auto"  # auto | jnp | fused (Pallas one-pass RMSNorm)
    # remat "auto": train() picks none/dots/full by an HBM-fit estimate at
    # startup and logs the choice (resolve_auto_knobs) — remat=none with a
    # fully-unrolled scan measured 1.5-2.6x faster than remat=full when it
    # fits (PERF.md); outside train() (sampling) "auto" behaves as none
    remat: str = "full"  # auto | full | dots | none  (model.py:149 uses full)
    scan_unroll: int = 1  # lax.scan unroll over layers; 0 = n_layer (full)
    # -- what follows leaves every config above byte-for-byte what it was --
    # a head width that is its own number (None = n_embd // n_head): the
    # projections are then D -> H*C and H*C -> D with H*C != D
    head_width: tp.Optional[int] = None
    # "layer" (LayerNorm) | "rms" (RMSNorm, eps 1e-6), both per head;
    # "rms_full": RMSNorm over the whole H*C projection, learned scale (OLMo 2)
    qk_norm_kind: str = "layer"
    # "interleaved": pairs (2i, 2i+1) rotate (GPT-J); "half": rotate_half,
    # the pairs are (i, i + C/2); "none": no rotary embedding
    rope_style: str = "interleaved"
    # the block norms and the final norm: learned scale or none; one eps for
    # all three (None = 1e-6 in the blocks, 1e-5 at the end, as midGPT)
    norm_scale: bool = False
    norm_eps: tp.Optional[float] = None
    # mlp="experts": dropless top-k routed SwiGLU experts (models/gpt
    # .ExpertMLP): `experts` of width `expert_hidden`, `experts_per_token`
    # chosen by an f32 softmax router, gates renormalised over the chosen
    experts: int = 0
    experts_per_token: int = 0
    expert_hidden: int = 0
    expert_renorm: bool = True
    # generation by diffusion over blocks (serving only): block_len > 0
    # makes attention causal across blocks of that many positions and
    # bidirectional inside one, and a decode step a denoising forward of a
    # block; block_steps denoising forwards reveal a block, block_len //
    # block_steps positions each; mask_token stands at unrevealed positions
    block_len: int = 0
    block_steps: int = 0
    mask_token: int = -1
    # layers of two kinds in one model: per layer "full_attention" or a
    # recurrent kind, "linear_attention" or "mamba" (None = every layer full
    # attention; one recurrent kind a model). A linear layer is a
    # gated-delta-rule mixer (models/gpt.GatedDeltaNet, ops/gated_delta):
    # ``linear_key_heads`` heads of ``linear_key_dim`` for q and k,
    # ``linear_value_heads`` of ``linear_value_dim`` for v and the state, a
    # causal depthwise convolution of ``linear_conv`` taps in front, beta in
    # (0, 2) with ``linear_neg_eigval``
    layer_types: tp.Optional[tp.Tuple[str, ...]] = None
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv: int = 4
    linear_neg_eigval: bool = False
    # "pre": x + f(norm(x)) (midGPT); "post": x + norm(f(x)), the norm on a
    # sub-layer's output before the residual add (OLMo 2's reordered norm)
    norm_order: str = "pre"
    # attention="latent": multi-head latent attention (models/gpt
    # .LatentAttention) in every layer — queries through a rank-``latent_q``
    # bottleneck with an RMSNorm, keys and values up-projected from one
    # normed ``latent_kv``-wide latent a token, ``latent_rope`` decoupled
    # rotary lanes a token shared by all heads beside ``latent_nope``
    # unrotated ones a head, values of ``latent_v`` a head. What is cached a
    # token a layer is the latent and the rotary key, nothing a head
    attention: str = "heads"  # "heads" | "latent"
    latent_q: int = 0
    latent_kv: int = 0
    latent_nope: int = 0
    latent_rope: int = 0
    latent_v: int = 0
    # the first ``dense_layers`` layers' MLP is a dense SwiGLU of
    # ``mlp_hidden`` whatever ``mlp`` says of the others (a stack of their
    # own: models/gpt.GPT.dense_blocks)
    dense_layers: int = 0
    # mlp="experts": the router's scoring, "softmax" over all experts or
    # "sigmoid" of each; ``expert_bias``: a learned per-expert bias added to
    # the scores for the CHOICE only, never to a weight; the chosen weights
    # times ``expert_scale``; ``shared_experts`` SwiGLU experts of
    # ``expert_hidden`` that every row goes through, added once
    expert_scoring: str = "softmax"
    expert_bias: bool = False
    expert_scale: float = 1.0
    shared_experts: int = 0
    # a "mamba" layer is a Mamba-1 selective scan (models/gpt.Mamba,
    # ops/selective_scan): ``mamba_expand * n_embd`` channels behind a causal
    # depthwise convolution of ``mamba_d_conv`` taps (a bias where
    # ``mamba_conv_bias``), a state of ``mamba_d_state`` a channel, the step
    # size through a rank-``mamba_dt_rank`` bottleneck
    mamba_d_state: int = 16
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    mamba_d_conv: int = 4
    mamba_conv_bias: bool = True

    def __post_init__(self):
        if self.layer_types is not None:
            kinds = tuple(self.layer_types)  # a JSON list: make it hashable
            assert len(kinds) == self.n_layer, (len(kinds), self.n_layer)
            assert set(kinds) <= {
                "full_attention", "linear_attention", "mamba"
            }, kinds
            assert {"linear_attention", "mamba"} - set(kinds), (
                "one recurrent kind a model", kinds
            )
            object.__setattr__(self, "layer_types", kinds)
        assert self.norm_order in ("pre", "post"), self.norm_order
        assert self.attention in ("heads", "latent"), self.attention
        assert self.expert_scoring in ("softmax", "sigmoid"), (
            self.expert_scoring
        )
        assert 0 <= self.dense_layers < self.n_layer, self.dense_layers
        if self.latent:
            assert self.layer_types is None, (
                "latent attention beside linear-attention layers has no form"
            )
            assert min(self.latent_q, self.latent_kv, self.latent_nope,
                       self.latent_rope, self.latent_v) >= 1, self

    @property
    def latent(self) -> bool:
        return self.attention == "latent"

    @property
    def layer_plan(self) -> tp.Tuple[tp.Tuple[str, int], ...]:
        """Per layer: its mixer's kind ("full" | "linear" | "latent") and its
        row of the cache of that kind (the page pool's layer axis counts the
        full or latent layers, the recurrent state's the recurrent ones:
        "linear" is either recurrent mixer, ``recurrent_mixer`` says which)."""
        kinds = self.layer_types or ("full_attention",) * self.n_layer
        seen = {"full": 0, "linear": 0, "latent": 0}
        plan = []
        for k in kinds:
            kind = "linear" if k in ("linear_attention", "mamba") else (
                "latent" if self.latent else "full"
            )
            plan.append((kind, seen[kind]))
            seen[kind] += 1
        return tuple(plan)

    @property
    def stack_plan(self) -> tp.Tuple[tp.Tuple[str, int], ...]:
        """Per layer: the stack of ``models.gpt.GPT`` that holds it — one a
        (mixer kind, MLP kind) present: ``dense_blocks`` (the leading
        ``dense_layers``), ``lin_blocks`` (linear attention), ``blocks`` (the
        others) — and its index there."""
        seen = {"dense_blocks": 0, "lin_blocks": 0, "blocks": 0}
        plan = []
        for n, (kind, _) in enumerate(self.layer_plan):
            stack = "dense_blocks" if n < self.dense_layers else (
                "lin_blocks" if kind == "linear" else "blocks"
            )
            plan.append((stack, seen[stack]))
            seen[stack] += 1
        return tuple(plan)

    @property
    def kv_layers(self) -> int:
        """Layers whose cache is pages of the pool."""
        return sum(1 for kind, _ in self.layer_plan if kind != "linear")

    @property
    def expert_layers(self) -> int:
        return self.n_layer - self.dense_layers if self.mlp == "experts" else 0

    @property
    def latent_row(self) -> int:
        """Lanes of a pooled latent row: the latent, then the rotary key,
        zero-padded to whole 128-lane tiles (the page the chip DMAs whole)."""
        return -(-(self.latent_kv + self.latent_rope) // 128) * 128

    @property
    def pool_heads(self) -> int:
        """The pool row's head axis: KV heads, or one for a latent row."""
        return 1 if self.latent else self.kv_heads

    @property
    def pool_width(self) -> int:
        return self.latent_row if self.latent else self.head_dim

    @property
    def rope_dim(self) -> int:
        """Lanes the rotary embedding turns."""
        return self.latent_rope if self.latent else self.head_dim

    @property
    def linear_layers(self) -> int:
        return self.n_layer - self.kv_layers

    @property
    def linear_channels(self) -> int:
        """Width of a linear layer's q | k | v projection, which is what its
        convolution runs over."""
        return (2 * self.linear_key_heads * self.linear_key_dim
                + self.linear_value_heads * self.linear_value_dim)

    @property
    def recurrent_mixer(self) -> str:
        """The recurrent layers' mixer: "gated_delta", "mamba", or "" for a
        model without recurrent layers."""
        kinds = set(self.layer_types or ())
        if "mamba" in kinds:
            return "mamba"
        return "gated_delta" if "linear_attention" in kinds else ""

    @property
    def mamba_inner(self) -> int:
        """A mamba layer's channels E."""
        return self.mamba_expand * self.n_embd

    @property
    def recurrent_geometry(self) -> tp.Tuple[tp.Tuple[int, ...],
                                             tp.Tuple[int, int]]:
        """One recurrent layer's cache of one slot: the shape of its float32
        state and of its convolution tail (the last ``taps - 1`` inputs).
        Gated delta rule: a ``[dk, dv]`` state a value head. Mamba: the
        ``[E, N]`` state laid as ``[E / L, N, L]``, E's lane tiles of ``L``
        (128 where E is a multiple of it) each holding the N states of its
        channels on sublanes, so that the tile has no padding."""
        if self.recurrent_mixer == "mamba":
            e, n = self.mamba_inner, self.mamba_d_state
            lanes = 128 if e % 128 == 0 else e
            return (e // lanes, n, lanes), (self.mamba_d_conv - 1, e)
        return (
            (self.linear_value_heads, self.linear_key_dim,
             self.linear_value_dim),
            (self.linear_conv - 1, self.linear_channels),
        )

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head if self.n_kv_head is not None else self.n_head

    @property
    def head_dim(self) -> int:
        if self.head_width is not None:
            return self.head_width
        assert self.n_embd % self.n_head == 0
        return self.n_embd // self.n_head


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device mesh axis sizes. -1 on at most one axis means "all remaining
    devices". Axis roles:
      replica  - pure DP, gradients all-reduced (DCN axis for multi-slice)
      fsdp     - DP + parameter/optimizer sharding (ZeRO-3)
      sequence - context parallelism (ring attention)
      tensor   - Megatron-style tensor parallelism
    """

    replica: int = 1
    fsdp: int = -1
    sequence: int = 1
    tensor: int = 1
    # GPipe pipeline stages (midgpt_tpu.parallel.pipeline); outermost axis
    pipeline: int = 1

    # number of slices for hybrid ICI/DCN meshes; 1 = single slice
    num_slices: int = 1

    # microbatches streamed through the pipeline per step (GPipe bubble =
    # (S-1)/(M+S-1)); 0 = auto (2 * pipeline stages)
    pp_microbatches: int = 0
    # dtype of activations crossing stage boundaries (inter-stage ppermute
    # + shard_map boundary). "float32" (default) works everywhere; a bf16
    # boundary halves ppermute bytes but crashes XLA CPU's
    # AllReducePromotion pass on the current pin (diagnosed r4: a bf16
    # manual-boundary all-reduce whose region root is a sharding
    # constraint cannot be cloned) — try "bfloat16" on real TPU hardware.
    pp_boundary_dtype: str = "float32"

    @property
    def axis_names(self) -> tp.Tuple[str, ...]:
        return ("pipeline", "replica", "fsdp", "sequence", "tensor")

    def sizes(self, n_devices: int) -> tp.Tuple[int, ...]:
        sizes = [self.pipeline, self.replica, self.fsdp, self.sequence, self.tensor]
        if -1 in sizes:
            known = 1
            for s in sizes:
                if s != -1:
                    known *= s
            assert n_devices % known == 0, (
                f"cannot infer -1 axis: {n_devices} devices, fixed product {known}"
            )
            sizes[sizes.index(-1)] = n_devices // known
        total = 1
        for s in sizes:
            total *= s
        assert total == n_devices, (
            f"mesh {sizes} does not cover {n_devices} devices"
        )
        return tuple(sizes)


@dataclasses.dataclass(frozen=True)
class ExperimentConfig:
    """Full experiment schema (parity: /root/reference/src/train.py:26-44)."""

    model: ModelConfig
    rundir: str = ""
    data_dir: str = ""
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    min_lr: float = 3e-5
    lr_decay_steps: int = 5000
    max_steps: int = 5000
    batch_size: int = 32  # GLOBAL batch size (train.py:31)
    g_accum_iters: int = 1
    # optimizer steps fused into ONE jitted lax.scan dispatch
    # (train.make_train_window): amortizes the fixed per-dispatch host/
    # runtime latency over K steps. 1 = today's one-dispatch-per-step
    # loop, bit-for-bit. K > 1 requires eval/ckpt intervals to be
    # multiples of K (resolve_dispatch_intervals — intervals get window
    # granularity) and holds a K-deep batch window in HBM.
    steps_per_dispatch: int = 1
    beta1: float = 0.9
    beta2: float = 0.95
    weight_decay: float = 1e-4
    grad_clip: float = 1.0
    independent_wd: bool = True  # add_decayed_weights(wd / lr) (train.py:156)
    eval_interval: int = 1000
    eval_batches: int = 200  # (train.py:110)
    # True: evaluate the SAME held-out batch sweep every interval (the
    # counter-based loader makes this free) — comparable, low-noise curves
    # for long runs. False (default) = reference parity: fresh random eval
    # batches each interval (train.py:110-116)
    eval_fixed: bool = False
    log_interval: int = 20  # wandb loss logging cadence (train.py:212)
    ckpt_interval: tp.Optional[int] = None  # None => eval_interval (train.py:143)
    ckpt_keep: int = 1  # max_to_keep (train.py:141)
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    seed: int = 0
    data_seed: int = 1234  # seeded loader (fixes train.py:60 nondeterminism)
    # T-chunk size for chunked cross-entropy (ops/loss.py): the [B,T,V] f32
    # logits never materialize. None = dense loss (reference parity path).
    # Works under a sharded sequence axis too: chunking runs shard-local
    # inside shard_map (train.py:129-140), so each rank chunks its own slice.
    loss_chunk: tp.Optional[int] = None
    # unroll the chunk scan: kills the while-loop overhead (carried [D,V]
    # dW re-read/written per backward iteration) while keeping per-chunk
    # logits checkpointed — measured win on the flagship shape (PERF.md r2)
    loss_chunk_unroll: bool = False
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    use_wandb: bool = False  # wandb.init on proc 0 (parity: launch.py:68)
    debug: bool = False
    # training-loop lifecycle tracing (midgpt_tpu.train_telemetry):
    # prefetch-wait / window launch+harvest / eval / checkpoint events +
    # Perfetto timeline + flight recorder, written into the rundir.
    # Tracing is loop-side only — the jitted window program is the
    # identical cached callable either way and the loss sequence is
    # bitwise unchanged (tests/test_train_telemetry.py). The anomaly
    # monitors run regardless of this flag (they only read scalars the
    # logging path already pulled to the host).
    train_telemetry: bool = False

    @property
    def microbatch_size(self) -> int:
        assert self.batch_size % self.g_accum_iters == 0
        return self.batch_size // self.g_accum_iters


# ---------------------------------------------------------------------------
# JSON round-trip (generic over the nested dataclasses above)
# ---------------------------------------------------------------------------


def to_dict(cfg: tp.Any) -> tp.Any:
    if dataclasses.is_dataclass(cfg) and not isinstance(cfg, type):
        return {f.name: to_dict(getattr(cfg, f.name)) for f in dataclasses.fields(cfg)}
    if isinstance(cfg, (list, tuple)):
        return [to_dict(v) for v in cfg]
    return cfg


def _from_dict(cls: tp.Any, data: tp.Any) -> tp.Any:
    if data is None:
        return None
    if dataclasses.is_dataclass(cls):
        kwargs = {}
        hints = tp.get_type_hints(cls)
        for f in dataclasses.fields(cls):
            if f.name not in data:
                continue
            ftype = hints[f.name]
            # unwrap Optional[X]
            origin = tp.get_origin(ftype)
            if origin is tp.Union:
                args = [a for a in tp.get_args(ftype) if a is not type(None)]
                ftype = args[0] if args else ftype
            if dataclasses.is_dataclass(ftype):
                kwargs[f.name] = _from_dict(ftype, data[f.name])
            else:
                kwargs[f.name] = data[f.name]
        return cls(**kwargs)
    return data


def to_json(cfg: ExperimentConfig) -> str:
    return json.dumps(to_dict(cfg), indent=2)


def from_json(s: str) -> ExperimentConfig:
    return _from_dict(ExperimentConfig, json.loads(s))


def from_dict(d: tp.Mapping[str, tp.Any]) -> ExperimentConfig:
    return _from_dict(ExperimentConfig, d)


# ---------------------------------------------------------------------------
# steps_per_dispatch interval resolution
# ---------------------------------------------------------------------------


def resolve_dispatch_intervals(cfg: ExperimentConfig) -> ExperimentConfig:
    """Validate/align the interval knobs against ``steps_per_dispatch``.

    With K steps fused into one dispatch, the host only sees the train
    state at window boundaries (multiples of K), so anything that needs
    the state *between* steps — eval sweeps, checkpoint saves — must land
    on the K grid. Misaligned explicit intervals FAIL FAST here with an
    actionable message instead of silently skewing the eval/ckpt cadence.

    ``log_interval`` needs no alignment: per-step (loss, grad-norm, lr)
    come back as stacked scan outputs of the fused window, so logging
    stays per-step exact at any cadence with at most one host sync per
    logging window. ``ckpt_interval=None`` resolves to ``eval_interval``
    (already validated). ``max_steps`` need not divide K — the final
    window is a shorter program (ceil(max_steps / K) dispatches total).

    K=1 returns ``cfg`` unchanged (the identical object): the trainer
    keeps today's one-dispatch-per-step loop and jitted step.
    """
    k = cfg.steps_per_dispatch
    if k == 1:
        return cfg
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")

    def _aligned(name: str, value: int) -> None:
        if value % k != 0:
            lo, hi = (value // k) * k, -(-value // k) * k
            suggestion = f"{hi}" if lo == 0 else f"{lo} or {hi}"
            raise ValueError(
                f"{name}={value} is not divisible by steps_per_dispatch={k}: "
                f"the fused window only exposes the train state every {k} "
                f"steps, so the {name.split('_')[0]} cadence would silently "
                f"skew to window boundaries. Set {name} to a multiple of {k} "
                f"(e.g. {suggestion}) or change steps_per_dispatch."
            )

    _aligned("eval_interval", cfg.eval_interval)
    if cfg.ckpt_interval is not None:
        _aligned("ckpt_interval", cfg.ckpt_interval)
    return cfg


# ---------------------------------------------------------------------------
# Named registry (parity: launch.py:25-27 dynamic import by name)
# ---------------------------------------------------------------------------

_REGISTRY: tp.Dict[str, tp.Callable[[], ExperimentConfig]] = {}


def register(name: str):
    def deco(fn: tp.Callable[[], ExperimentConfig]):
        _REGISTRY[name] = fn
        return fn

    return deco


def get_config(name: str, **overrides) -> ExperimentConfig:
    # populate registry
    from midgpt_tpu import configs as _  # noqa: F401

    if name not in _REGISTRY:
        raise KeyError(f"unknown config {name!r}; have {sorted(_REGISTRY)}")
    cfg = _REGISTRY[name]()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def list_configs() -> tp.List[str]:
    from midgpt_tpu import configs as _  # noqa: F401

    return sorted(_REGISTRY)
