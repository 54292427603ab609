"""Static HBM traffic auditor for the serving programs.

Serving decode is HBM-bound at every practical batch (PERF.md r5), so
its performance floor is a BYTES budget: the weight stream + the live
KV stream, per decode step, against the chip's HBM bandwidth. Two
shipped bug classes silently changed those bytes without changing any
output: PR 6's closed-over-model constant folding (weights baked into
the executable — and, quantized, folded back to full f32, doubling the
exact stream the int8 path halves) and the PR 7 class of partitioner
"help" (a sharded buffer regathered through a page gather, multiplying
the per-chip stream by tp). Each was caught by a hand-written rule that
happened to match its HLO shape; this module generalizes both into a
BYTE budget: compute the streams from the compiled program's entry
interface, and gate them against checked-in expectations
(:mod:`midgpt_tpu.analysis.budgets`) — any regression that
re-materializes or re-gathers a large buffer moves bytes and trips the
gate, regardless of what the HLO looks like.

Two layers, both jax-free:

- **HLO streams** (:func:`traffic_report`): classify every entry
  parameter of the compiled program into weight / KV-pool / logits /
  control streams by (dtype, shape) against the live trees' keys
  (:func:`stream_keys` — the harness builds these from the very model/
  pool/logits it compiled), and count large CONSTANTS separately — a
  weight that stops being an entry parameter did not stop streaming,
  it moved into the executable, which is exactly the PR 6 bug.
- **Roofline floor** (:func:`floor_decomposition`): the analytic
  bytes-per-step decomposition (weights + live KV + logits) and its
  ms floor at a given HBM bandwidth (``count_params * 2`` bytes of bf16
  weights + ``L * S * Hkv * live * C * 2 * 2`` bytes of K and V), so
  PERF.md's floor table is generated, not hand-computed.

Accounting note (found by writing this auditor): PERF.md's r5 prose
stated the 124M B=8 KV stream as ~0.12 ms, which counts the K and V
planes as ONE stream; both are read every step (K for scores, V for
the value sum), so the decomposition below reports ~0.24 ms at the same
geometry and the regenerated PERF table carries the corrected total.
"""

from __future__ import annotations

import dataclasses
import re
import typing as tp

from midgpt_tpu.analysis import hlo as hlo_mod

ShapeT = tp.Tuple[int, ...]
KeyT = tp.Tuple[str, ShapeT]  # (hlo dtype, shape)

STREAMS = ("weights", "kv", "logits", "control", "constants")

# jax dtype name -> HLO primitive type (entry-parameter classification
# compares live pytree leaves against parsed HLO shapes)
_JAX_TO_HLO_DTYPE = {
    "bfloat16": "bf16", "float16": "f16", "float32": "f32",
    "float64": "f64", "int8": "s8", "uint8": "u8", "int16": "s16",
    "int32": "s32", "int64": "s64", "uint32": "u32", "uint64": "u64",
    "bool": "pred",
}

_CONST_RE = re.compile(
    r"=\s*([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{[^}]*\})?\s+constant\("
)


def hlo_dtype(jax_dtype) -> str:
    """'bfloat16' (or a numpy dtype) -> 'bf16'."""
    name = str(jax_dtype)
    return _JAX_TO_HLO_DTYPE.get(name, name)


def parse_large_constants(
    hlo: str, *, min_bytes: int = 4096
) -> tp.List[KeyT]:
    """Every ``constant(...)`` instruction in the module whose buffer is
    at least ``min_bytes`` — below that sit iota tables, norm epsilons
    and mask literals (legitimate); above it sits baked-in model state
    (the PR 6 closed-over-model bug class)."""
    out: tp.List[KeyT] = []
    for line in hlo.splitlines():
        m = _CONST_RE.search(line)
        if not m:
            continue
        dtype = m.group(1)
        shape = tuple(int(x) for x in m.group(2).split(",") if x != "")
        if hlo_mod.shape_bytes(dtype, shape) >= min_bytes:
            out.append((dtype, shape))
    return out


@dataclasses.dataclass(frozen=True)
class TrafficReport:
    """Per-dispatch HBM stream decomposition of one compiled program."""

    program: str
    streams: tp.Mapping[str, int]  # bytes per stream (entry interface)
    window_steps: int  # model steps per dispatch (the K-step scan)
    comms_bytes: int  # collective wire bytes per dispatch (sharded)
    unclassified: tp.Tuple[KeyT, ...]  # float params matching no key set

    @property
    def weights_bytes_per_dispatch(self) -> int:
        """The weight stream is re-read by every step of the fused
        window scan — per dispatch it pays ``window_steps`` times."""
        return self.streams["weights"] * self.window_steps

    def to_dict(self) -> tp.Dict[str, tp.Any]:
        return {
            "program": self.program,
            "streams": dict(self.streams),
            "window_steps": self.window_steps,
            "weights_bytes_per_dispatch": self.weights_bytes_per_dispatch,
            "comms_bytes": self.comms_bytes,
            "unclassified": [
                f"{d}[{','.join(map(str, s))}]" for d, s in self.unclassified
            ],
        }


def traffic_report(
    hlo: str,
    *,
    program: str,
    stream_keys: tp.Mapping[str, tp.Collection[KeyT]],
    window_steps: int = 1,
    comms_bytes: int = 0,
    min_const_bytes: int = 4096,
) -> TrafficReport:
    """Classify the compiled program's entry parameters into streams.

    ``stream_keys`` maps ``weights`` / ``kv`` / ``logits`` to the
    (dtype, shape) keys of the live trees the program was compiled
    against (shard-LOCAL shapes under a mesh — the partitioned HLO
    contains those). Integer/bool parameters are ``control`` (block
    tables, masks, lengths); float parameters matching no key set are
    reported as ``unclassified`` rather than silently binned — an
    unexplained large float input is itself a finding."""
    params = hlo_mod.parse_entry_parameters(hlo)
    weight_keys = frozenset(stream_keys.get("weights", ()))
    kv_keys = frozenset(stream_keys.get("kv", ()))
    logit_keys = frozenset(stream_keys.get("logits", ()))
    streams = {s: 0 for s in STREAMS}
    unclassified: tp.List[KeyT] = []
    for dtype, shape in params:
        nbytes = hlo_mod.shape_bytes(dtype, shape)
        key = (dtype, shape)
        if key in weight_keys:
            streams["weights"] += nbytes
        elif key in kv_keys:
            streams["kv"] += nbytes
        elif key in logit_keys:
            streams["logits"] += nbytes
        elif dtype in ("s8", "bf16", "f16", "f32", "f64"):
            # s8 counts as a potential weight dtype: an s8 param that
            # matches no expected shape is just as suspicious
            if nbytes >= min_const_bytes:
                unclassified.append(key)
            else:
                streams["control"] += nbytes
        else:
            streams["control"] += nbytes
    for dtype, shape in parse_large_constants(
        hlo, min_bytes=min_const_bytes
    ):
        streams["constants"] += hlo_mod.shape_bytes(dtype, shape)
    return TrafficReport(
        program=program,
        streams=streams,
        window_steps=window_steps,
        comms_bytes=comms_bytes,
        unclassified=tuple(unclassified),
    )


# ---------------------------------------------------------------------------
# analytic roofline floor (config arithmetic, no HLO needed)
# ---------------------------------------------------------------------------


def _mlp_hidden(cfg) -> int:
    # mirrors models.gpt.mlp_hidden_dim without importing jax: pinned
    # width, else ratio*D rounded UP to a multiple of 256 when fractional
    if cfg.mlp_hidden is not None:
        return cfg.mlp_hidden
    f = cfg.mlp_ratio * cfg.n_embd
    if f == int(f):
        return int(f)
    return 256 * -(-int(f) // 256)


def weight_stream_bytes(cfg, *, quant: bool = False) -> int:
    """Bytes of model weights ONE decode step streams from HBM.

    Counts every matrix a decode forward contracts against: the block
    projections and the lm head ([D, V] — counted once; the embedding
    side of a tied/init-tied pair is a B-row GATHER, not a stream),
    plus the small norm vectors. Matches ``count_params(model) * 2``
    to within the norm vectors at bf16, and prices the int8 path as s8 matrices + f32
    per-output-channel scales (midgpt_tpu.quant)."""
    assert cfg.mlp in ("gelu", "swiglu"), (
        f"analytic weight stream covers dense MLPs, got {cfg.mlp!r}"
    )
    d, c = cfg.n_embd, cfg.head_dim
    h, hkv = cfg.n_head, cfg.kv_heads
    f = _mlp_hidden(cfg)
    qkv_out = (h + 2 * hkv) * c
    gate = 1 if cfg.mlp == "swiglu" else 0
    # per-layer matmul element counts and their per-matrix OUT dims
    mats = [
        (d * qkv_out, qkv_out),  # wqkv
        (h * c * d, d),  # wo
        (d * f, f),  # w_up
        (f * d, d),  # w_down
    ] + [(d * f, f)] * gate
    head = (d * cfg.vocab_size, cfg.vocab_size)
    norm_bytes = 0
    if cfg.qk_norm:
        # q/k LayerNorms: one [C] scale each per layer, model dtype
        norm_bytes += cfg.n_layer * 2 * c * 2
    if quant:
        per_layer = sum(n for n, _ in mats) * 1  # s8
        per_layer += sum(out for _, out in mats) * 4  # f32 scales
        head_bytes = head[0] * 1 + head[1] * 4
    else:
        per_layer = sum(n for n, _ in mats) * 2  # bf16
        head_bytes = head[0] * 2
    return cfg.n_layer * per_layer + head_bytes + norm_bytes


def kv_stream_bytes(
    cfg, *, slots: int, live_tokens: float, cache_bytes: int = 2
) -> int:
    """Bytes of KV cache ONE decode step streams: every slot's live
    context, K for the scores and V for the value sum, all layers."""
    return int(
        cfg.n_layer * slots * cfg.kv_heads * live_tokens * cfg.head_dim
        * cache_bytes * 2  # K and V are both read
    )


def floor_decomposition(
    cfg,
    *,
    slots: int,
    live_tokens: tp.Optional[float] = None,
    quant: bool = False,
    kv_quant: bool = False,
    cache_bytes: int = 2,
    page_size: int = 16,
    hbm_gbps: float = 800.0,
    tp_degree: int = 1,
) -> tp.Dict[str, tp.Any]:
    """The static bytes-per-step roofline for one serving geometry:
    weight + KV + logits streams, bytes per token, and the ms/step HBM
    floor at ``hbm_gbps``. ``live_tokens`` defaults to ``block_size``
    (the fully-grown worst case); pass a trace mean for a workload
    floor. Under TP the weight and KV streams are per-CHIP (1/tp each
    — column/row-parallel weights, whole-KV-head pool sharding); the
    cross-chip wire bytes are cost_report territory, not HBM.
    ``kv_quant`` prices the int8 paged pool: 1-byte K/V elements plus
    the f32 per-(page, KV-head) scale planes of the live pages (one
    f32 per plane per K and V — ``page_size`` sets how many positions
    share a scale)."""
    live = float(
        cfg.block_size if live_tokens is None else live_tokens
    )
    w = weight_stream_bytes(cfg, quant=quant) // tp_degree
    kv_bytes = 1 if kv_quant else cache_bytes
    kv = kv_stream_bytes(
        cfg, slots=slots, live_tokens=live, cache_bytes=kv_bytes
    ) // tp_degree
    if kv_quant:
        # per-page dequant scales: live pages x KV heads x f32, K and V
        live_pages = -(-int(live) // page_size)
        kv += (
            cfg.n_layer * slots * live_pages * cfg.kv_heads * 4 * 2
        ) // tp_degree
    # the carried [S, V] f32 logits are read (sampling) and written
    # (carry) once per step; vocab-sharded under TP
    logits = 2 * slots * cfg.vocab_size * 4 // tp_degree
    total = w + kv + logits
    to_ms = 1e3 / (hbm_gbps * 1e9)
    return {
        "slots": slots,
        "live_tokens": live,
        "quant": quant,
        "kv_quant": kv_quant,
        "tp": tp_degree,
        "hbm_gbps": hbm_gbps,
        "weights_bytes_per_step": w,
        "kv_bytes_per_step": kv,
        "logits_bytes_per_step": logits,
        "bytes_per_step": total,
        "bytes_per_token": total // slots,
        "weights_floor_ms": round(w * to_ms, 4),
        "kv_floor_ms": round(kv * to_ms, 4),
        "floor_ms_per_step": round(total * to_ms, 4),
        # per emitted token (a full-occupancy decode step emits one
        # token per slot): the numerator of the serving attainment
        # fraction — attainment = floor_ms_per_token / measured ms/tok.
        # Significant digits, not decimals: tiny CPU test geometries
        # sit at ~1e-5 ms and must not round to a hard zero.
        "floor_ms_per_token": float(f"{total * to_ms / slots:.4g}"),
    }


def train_param_count(cfg) -> int:
    """Analytic parameter count of a dense GPT config (jax-free mirror
    of ``models.gpt.count_params`` PLUS the embedding table — the
    optimizer state streams the embedding too, so the training-step
    byte floor counts it even though the FLOP accounting doesn't)."""
    assert cfg.mlp in ("gelu", "swiglu"), (
        f"analytic train floor covers dense MLPs, got {cfg.mlp!r}"
    )
    d, c = cfg.n_embd, cfg.head_dim
    f = _mlp_hidden(cfg)
    qkv_out = (cfg.n_head + 2 * cfg.kv_heads) * c
    per_layer = (
        d * qkv_out + cfg.n_head * c * d
        + (3 if cfg.mlp == "swiglu" else 2) * d * f
    )
    return cfg.n_layer * per_layer + 2 * cfg.vocab_size * d


#: Bytes of HBM traffic one optimizer step moves per parameter under
#: the donated f32-Adam step: f32 params read+written (8) + Adam m,v
#: read+written (16) + the f32 grad written then read by the update (8)
#: + the bf16 compute-cast copy written then re-read by the backward
#: (4). Deliberately coarse (activations excluded — they are the
#: compute side's concern) but stated, so the floor is reproducible
#: arithmetic rather than folklore.
TRAIN_STATE_BYTES_PER_PARAM = 36


def train_floor_decomposition(
    cfg,
    *,
    batch_size: int,
    n_devices: int = 1,
    flops_per_token: float,
    peak_flops_per_device: float,
    hbm_gbps: float = 800.0,
    state_shards: tp.Optional[int] = None,
) -> tp.Dict[str, tp.Any]:
    """The static per-step roofline for one TRAINING geometry: the
    compute floor (model FLOPs at the chip's peak — what MFU is
    measured against) and the optimizer-state HBM floor
    (:data:`TRAIN_STATE_BYTES_PER_PARAM` per parameter, sharded over
    ``state_shards`` — defaults to ``n_devices``, the FSDP default),
    combined as ``floor_ms_per_step = max(compute, hbm)``. The
    attainment fraction a measured step carries is
    ``floor_ms_per_step / measured_step_ms`` — 1.0 means the hardware
    ceiling, and for the compute-bound training regime it tracks MFU by
    construction. ``flops_per_token``/``peak_flops_per_device`` are
    passed in so this stays jax-free (utils.metrics wires the
    device-dependent values)."""
    n_params = train_param_count(cfg)
    shards = max(1, n_devices if state_shards is None else state_shards)
    hbm_bytes = n_params * TRAIN_STATE_BYTES_PER_PARAM // shards
    tokens_per_step = batch_size * cfg.block_size
    compute_ms = (
        tokens_per_step * flops_per_token
        / (peak_flops_per_device * max(1, n_devices)) * 1e3
    )
    hbm_ms = hbm_bytes / (hbm_gbps * 1e9) * 1e3
    return {
        "n_params": n_params,
        "tokens_per_step": tokens_per_step,
        "hbm_gbps": hbm_gbps,
        "train_state_bytes_per_step": hbm_bytes,
        "train_compute_floor_ms": round(compute_ms, 4),
        "train_hbm_floor_ms": round(hbm_ms, 4),
        "train_floor_ms_per_step": round(max(compute_ms, hbm_ms), 4),
        "train_floor_bound": (
            "compute" if compute_ms >= hbm_ms else "hbm"
        ),
    }


def floor_table_markdown(rows: tp.Sequence[tp.Dict[str, tp.Any]]) -> str:
    """Render floor decompositions as the PERF.md markdown table. The
    CI serving-audit job regenerates this; PERF.md carries the output
    verbatim, so the published floor numbers can never drift from the
    auditor's arithmetic."""
    lines = [
        "| geometry | weights MB | KV MB | bytes/token | weights ms "
        "| KV ms | floor ms/step |",
        "|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        geom = (
            f"B={r['slots']} live={int(r['live_tokens'])}"
            f"{' int8' if r['quant'] else ' bf16'}"
            + (" kv8" if r.get("kv_quant") else "")
            + (f" tp={r['tp']}" if r.get("tp", 1) > 1 else "")
        )
        lines.append(
            f"| {geom} "
            f"| {r['weights_bytes_per_step'] / 1e6:.1f} "
            f"| {r['kv_bytes_per_step'] / 1e6:.1f} "
            f"| {r['bytes_per_token']:,} "
            f"| {r['weights_floor_ms']:.3f} "
            f"| {r['kv_floor_ms']:.3f} "
            f"| {r['floor_ms_per_step']:.3f} |"
        )
    return "\n".join(lines)


def train_budget_table_markdown(
    budgets: tp.Mapping[tp.Tuple[str, int], tp.Mapping[str, tp.Any]],
) -> str:
    """Render the checked-in train traffic cells
    (:data:`midgpt_tpu.analysis.budgets.TRAIN_BUDGETS`) as the PERF.md
    markdown table — one row per (mesh geometry, window K) cell, with
    the ICI/DCN tier split and the per-axis decomposition. Generated
    from the budget dict itself, so the published numbers can never
    drift from what CI gates. jax-free."""
    lines = [
        "| geometry | K | ICI MB/step | DCN MB/step | by axis |",
        "|---|---|---|---|---|",
    ]
    for (geom, k), cell in sorted(budgets.items()):
        axes = ", ".join(
            f"{a}: {b / 1e6:.1f}"
            for a, b in sorted(cell.get("by_axis", {}).items())
        )
        lines.append(
            f"| {geom} | {k} "
            f"| {cell['ici_bytes'] / 1e6:.1f} "
            f"| {cell['dcn_bytes'] / 1e6:.1f} "
            f"| {axes} |"
        )
    return "\n".join(lines)
